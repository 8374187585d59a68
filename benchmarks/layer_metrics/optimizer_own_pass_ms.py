"""Device time per step of the optimizer's passes of their own: the ops of a
trace whose HLO instruction carries the step's named scope ``optimizer``
(``train/sharded_lm.py``: ``tx.update`` and ``apply_updates``). An update
that XLA fuses into a weight-gradient product is given the product's
``op_name`` and is not in it. Through the instruction -> ``op_name`` map that
``mla_proj_ms.scoped_seconds`` reads: a program without the scope, or a
runner without the map, reports nothing."""

from harness.spec import load_module

UNIT = "ms"
SCOPES = r"(?:^|/)optimizer(?:/|$)"


def compute(record, trace):
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, trace, SCOPES
    )
    return None if found is None else 1e3 * found[0] / found[2]
