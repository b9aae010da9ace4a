"""Engine step (serving/batched_engine.py): share of the matvec MACs the
chunk dispatches issued in the window that multiply weights the model
holds, in percent (the boundary samples' ``matvec_macs_held`` over
``matvec_macs``, tallied on the host from the pack's shapes).  The dense
mirror multiplies every [4H, D+H] entry: the rest are pruned slots of a
CBTD stack or the structural-zero blocks of a DeltaGRU stack."""


def read(run):
    macs = sum(s.get("matvec_macs", 0) for s in run.timeseries)
    held = sum(s.get("matvec_macs_held", 0) for s in run.timeseries)
    return 100.0 * held / macs if macs else None
