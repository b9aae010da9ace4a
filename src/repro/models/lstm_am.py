"""LSTM acoustic model — the paper's own network family (Sec. V-B).

"LSTM layers are followed by a Fully-Connected Layer having the same
number of units with each LSTM layer and a final logit layer."  Trained
with CTC; supports the pretrain (plain LSTM + CBTD) and retrain
(DeltaLSTM, alpha=1) phases, INT8/INT16 fake-quant, and exposes delta
statistics for the hardware model.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import (
    apply_cbtd,
    delta_lstm_layer,
    fake_quant_act_ste,
    fake_quant_ste,
    init_gru_params,
    init_lstm_params,
    lstm_layer,
    stacked_weight_matrix,
    QuantConfig,
)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LSTMAMConfig:
    input_dim: int = 123
    hidden_dim: int = 1024
    n_layers: int = 2
    n_classes: int = 41          # CTC vocab (blank + phonemes)
    delta: bool = False          # DeltaLSTM (retrain phase) vs LSTM (pretrain)
    theta: float = 0.0           # delta threshold
    quant: QuantConfig = QuantConfig(enabled=False)
    # recurrent cell the serving pack builds: "lstm" (params["lstm"]) or
    # "gru" (params["gru"], core/delta_gru.py layers); training is LSTM-only
    cell: str = "lstm"

    @property
    def name(self) -> str:
        kind = "LSTM" if self.cell == "lstm" else self.cell.upper()
        kind = f"Delta{kind}" if self.delta else kind
        return f"{kind}-{self.n_layers}L-{self.hidden_dim}H-UNI"


def init_params(key: jax.Array, cfg: LSTMAMConfig, dtype=jnp.float32) -> Params:
    """Recurrent layers under ``params[cfg.cell]`` (LSTM or DeltaGRU
    layers), then the FCL and logit layer."""
    keys = jax.random.split(key, cfg.n_layers + 2)
    init_layer = init_gru_params if cfg.cell == "gru" else init_lstm_params
    layers = []
    d = cfg.input_dim
    for i in range(cfg.n_layers):
        layers.append(init_layer(keys[i], d, cfg.hidden_dim, dtype))
        d = cfg.hidden_dim
    bound = 1.0 / jnp.sqrt(cfg.hidden_dim)
    fcl = {
        "w": jax.random.uniform(
            keys[-2], (cfg.hidden_dim, cfg.hidden_dim), dtype, -bound, bound
        ),
        "b": jnp.zeros((cfg.hidden_dim,), dtype),
    }
    logit = {
        "w": jax.random.uniform(
            keys[-1], (cfg.n_classes, cfg.hidden_dim), dtype, -bound, bound
        ),
        "b": jnp.zeros((cfg.n_classes,), dtype),
    }
    return {cfg.cell: layers, "fcl": fcl, "logit": logit}


def n_params(params: Params) -> int:
    return sum(l.size for l in jax.tree.leaves(params))


def _maybe_quant_params(params: Params, cfg: LSTMAMConfig) -> Params:
    if not cfg.quant.enabled:
        return params

    def q(leaf):
        if leaf.ndim == 2:
            return fake_quant_ste(leaf, cfg.quant.weight_bits)
        return leaf

    return jax.tree.map(q, params)


def _maybe_quant_act(x: jax.Array, cfg: LSTMAMConfig) -> jax.Array:
    if not cfg.quant.enabled:
        return x
    return fake_quant_act_ste(x, cfg.quant.act_bits, cfg.quant.act_frac_bits)


def forward(
    params: Params, cfg: LSTMAMConfig, feats: jax.Array,
    collect_aux: bool = False,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """feats: [B, T, D] -> logits [B, T, n_classes]; aux carries per-layer
    delta occupancy (for sparsity stats / hwsim) when collect_aux."""
    params = _maybe_quant_params(params, cfg)
    x = feats
    aux: Dict[str, Any] = {"layers": []}

    for li, lp in enumerate(params["lstm"]):
        x = _maybe_quant_act(x, cfg)
        if cfg.delta:
            def run(seq, lp=lp):
                return delta_lstm_layer(lp, seq, cfg.theta)
            hs, _, layer_aux = jax.vmap(run)(x)
            if collect_aux:
                aux["layers"].append(
                    {"nnz_dx": layer_aux["nnz_dx"], "nnz_dh": layer_aux["nnz_dh"],
                     "dx_masks": layer_aux["dx_masks"],
                     "dh_masks": layer_aux["dh_masks"]}
                )
        else:
            hs = jax.vmap(lambda seq, lp=lp: lstm_layer(lp, seq))(x)
        x = hs

    x = _maybe_quant_act(x, cfg)
    x = jax.nn.relu(x @ params["fcl"]["w"].T + params["fcl"]["b"])
    x = _maybe_quant_act(x, cfg)
    logits = x @ params["logit"]["w"].T + params["logit"]["b"]
    return logits, aux


def cbtd_prune_stacks(params: Params, gamma: float, m: int) -> Params:
    """CBTD-prune every LSTM layer's stacked [4H, D+H] matrix (the exact
    matrix the serving engines CBCSC-pack) and split it back into
    w_x / w_h.  Returns new params; fcl/logit pass through untouched.
    Used by benchmarks/examples/tests that need a servable (column-
    balanced) model without running the full pretrain/retrain loop."""
    out = dict(params)
    layers = []
    for lp in params["lstm"]:
        w = apply_cbtd(stacked_weight_matrix(lp), gamma=gamma, m=m)
        d = lp["w_x"].shape[1]
        layers.append({**lp, "w_x": w[:, :d], "w_h": w[:, d:]})
    out["lstm"] = layers
    return out


def lstm_weight_layout() -> Dict[str, Any]:
    """CBTD layout: prune the recurrent stacks + FCL (paper Sec. V-C:
    'The CBTD was also applied to the FCL'), never the logit layer."""
    from repro.core.cbtd import CBTDConfig

    return {"w_x": CBTDConfig(), "w_h": CBTDConfig(), "fcl/w": CBTDConfig()}
