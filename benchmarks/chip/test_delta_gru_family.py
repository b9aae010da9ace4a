"""CPU checks of the DeltaGRU family (``models/delta_gru.py``) and its
cell: the work per frame against a hand count, the plain reference
against ``repro.core.delta_gru``, and the whole cell at a tiny width.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import reference  # noqa: E402
import run  # noqa: E402
import speech  # noqa: E402
import work  # noqa: E402

GRU = run.load_model("delta_gru")
CFG = json.loads((HERE / "configs" / "gru_2l_768h.json").read_text())
MIX = json.loads((HERE / "traffic" / "offline.json").read_text())


def test_work_matches_the_hand_count():
    # GRU stacks 3*768*(123+768) + 3*768*(768+768), FCL 768^2, logit 41*768
    assert GRU.weights_held(CFG) == 6_213_120
    assert work.ops_per_frame(GRU, CFG) == 12_426_240


def test_weights_are_dense_on_the_int8_grid():
    cfg = dict(CFG, hidden_dim=64, input_dim=20)
    params = GRU.make_params(7, cfg)
    step, qmax = reference.grid(cfg["weights"]["gru_scale"] / 8.0)
    for lp in params["gru"]:
        for w in (lp["w_x"], lp["w_h"]):
            codes = np.asarray(w) / step
            assert np.all(codes == np.rint(codes))
            assert np.abs(codes).max() == qmax
            assert np.mean(codes != 0) > 0.95


def test_forward_at_theta_zero_is_the_plain_gru():
    """At theta 0 every delta fires: the delta memories telescope to the
    GRU pre-activations of ``core.delta_gru.gru_layer``.  Tolerance
    1e-4: T = 60 frames of summed deltas against one product, both at
    "highest" precision."""
    import jax
    import jax.numpy as jnp
    from repro.core.delta_gru import gru_layer

    cfg = dict(CFG, hidden_dim=64)
    params = GRU.make_params(5, cfg)
    utts = speech.utterances(3, np.array([60, 45]), MIX["speech"])
    got, _ = reference.reference_logits(
        GRU, params, utts, dict(cfg, theta=0.0), precision="highest")
    with jax.default_matmul_precision("highest"):
        for u, g in zip(utts, got):
            hs = jnp.asarray(u)
            for lp in params["gru"]:
                hs = gru_layer(lp, hs)
            y = jax.nn.relu(hs @ params["fcl"]["w"].T + params["fcl"]["b"])
            want = y @ params["logit"]["w"].T + params["logit"]["b"]
            np.testing.assert_allclose(g, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_runs_correct_at_a_tiny_width(trace):
    """The cell through the whole harness on the CPU: hidden 64, m 8, a
    pool of 8; the served logits meet the configuration's limits against
    the family's ``forward``, and the held share reads 3 rows in 4."""
    cell = run.load_cell("gru_2l_768h.offline")
    assert cell.model.__name__ == "bench_model_delta_gru"
    cell.config = dict(cell.config, hidden_dim=64, m=8)
    cell.mix = dict(cell.mix, capacity=8, chunk_frames=8, max_frames=128,
                    n_distinct=16, ramp_completions=8, sample=4)
    cell.mix["length"] = dict(cell.mix["length"], median=40, min=10,
                              max=80)
    res = run.run_cell(cell, 2**31 + 77, 1.0, trace, require_chip=False,
                       use_cache=False, log=lambda _m: None)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    if trace:
        assert res["metrics"]["matvec_held_share"]["value"] == 75.0
    else:
        assert {"frames_per_s", "setup_s"} <= set(res["metrics"])
