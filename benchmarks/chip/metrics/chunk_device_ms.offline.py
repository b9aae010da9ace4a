"""Engine step (batched_engine._step_chunk): mean device milliseconds per
execution of the chunk program in the traced window of an offline cell."""


def read(run):
    return run.module_ms("step_chunk")
