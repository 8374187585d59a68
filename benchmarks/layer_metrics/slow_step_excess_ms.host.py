"""Over the window's slow steps (``slow_steps``), the sum of the time of the
root span outside ``trainer.step.fetch`` (``place``, ``dispatch``, the root's
own) less its median over the window: how much longer the host made them.
0 where none is slow."""

from harness.spec import load_module

UNIT = "ms"


def compute(record, trace):
    spans = load_module("layer_metrics", "step_span_ms_p50")
    return spans.slow_excess_ms(
        record, lambda s: spans.seconds(s["step"]) - spans.seconds(s["fetch"])
    )
