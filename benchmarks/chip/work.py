"""Work per frame of a configuration, counted from the algorithm's shapes.

``ops_per_frame`` is what ``step_mfu`` credits: 2 operations (a multiply
and an add) for every weight the model holds, once per frame.  That is the
CBTD-pruned LSTM stacks (every subcolumn of ``4H / m`` rows keeps
``S - floor(S * gamma)`` weights) plus the dense FCL and logit layer.
Temporal sparsity is not credited, so no route can push the share past
the peak, and the count does not depend on what implements the step.
"""
from __future__ import annotations

from typing import Dict, List, Tuple


def _layers(cfg: Dict) -> List[Tuple[int, int]]:
    d, h = cfg["input_dim"], cfg["hidden_dim"]
    return [(d if i == 0 else h, h) for i in range(cfg["n_layers"])]


def lstm_weights(cfg: Dict) -> int:
    """Nonzero weights of the CBTD-pruned stacked LSTM matrices."""
    total = 0
    for d, h in _layers(cfg):
        s = 4 * h // cfg["m"]
        keep = s - int(s * cfg["gamma"])
        total += (d + h) * cfg["m"] * keep
    return total


def head_weights(cfg: Dict) -> int:
    h = cfg["hidden_dim"]
    return h * h + cfg["n_classes"] * h


def ops_per_frame(cfg: Dict) -> float:
    return 2.0 * (lstm_weights(cfg) + head_weights(cfg))

