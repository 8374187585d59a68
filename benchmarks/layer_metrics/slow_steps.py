"""Steps of the window whose root span ``trainer.step`` exceeds 1.15 x the
window's median: the rule of the harness's own ``slow`` list, counted from
inside ``train_step``."""

from harness.spec import load_module

UNIT = "count"


def compute(record, trace):
    found = load_module("layer_metrics", "step_span_ms_p50").slow_steps(record)
    return None if found is None else len(found[0])
