"""The program's own clock around each step of the window (the root span
``trainer.step``): the 0.9 quantile, in ms; ``step_ms_p90`` from inside."""

from harness.spec import load_module

UNIT = "ms"


def compute(record, trace):
    return load_module("layer_metrics", "step_span_ms_p50").step_ms_quantile(record, 0.9)
