"""Engine step: the chunk program's share of the chip's bf16 peak, in
percent.  The work of one execution (every slot of the pool, every frame
of the chunk, times the operations per frame that ``work.ops_per_frame``
credits: 2 per weight held, temporal sparsity not credited) over its mean
device time in the trace, over the peak of ``peaks.json``."""


def read(run):
    ms = run.module_ms("step_chunk")
    if not ms or run.peaks is None:
        return None
    ops = run.frames_per_chunk * run.ops_per_frame
    return 100.0 * ops / (ms * 1e-3) / run.peaks["bf16_flops_per_s"]
