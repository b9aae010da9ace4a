"""Work per frame of a configuration, counted from the algorithm's shapes.

``ops_per_frame`` is what ``step_mfu`` credits: 2 operations (a multiply
and an add) for every weight the model holds, once per frame.  The family
module of the configuration (``models/<family>.py``) counts the weights
from its shapes in ``weights_held``: the pruned recurrent stacks plus the
dense head.  Temporal sparsity is not credited, so no route can push the
share past the peak, and the count does not depend on what implements the
step.
"""
from __future__ import annotations

from typing import Dict


def ops_per_frame(model, cfg: Dict) -> float:
    return 2.0 * model.weights_held(cfg)
