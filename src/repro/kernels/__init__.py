"""Pallas TPU kernels for the Spartus compute hot-spots.

delta_encode    — DPE: thresholded delta + reference update (Fig. 6)
stsp_spmv       — MAC arrays: spatio-temporal sparse MxV over CBCSC (Fig. 2/9)
lstm_pointwise  — HPE: fused gate nonlinearities + cell update (Fig. 8)

Each kernel is batched over a leading slot dimension, has a pure-jnp
oracle in ref.py and a jit'd wrapper (with XLA fallback) in ops.py, and is
built through lowering.pallas_call (compiled on TPU, interpreted only when
lowered for the CPU).  See tests/test_kernels.py for the shape/dtype
sweeps and tests/test_tpu_compile.py for the v5e compiles.
"""
from repro.kernels import ops, ref
from repro.kernels.delta_encode import delta_encode_pallas
from repro.kernels.lstm_pointwise import lstm_pointwise_pallas
from repro.kernels.stsp_spmv import stsp_spmv_scatter_batch_pallas
