"""The part of ``host_gap_ms.train``, per traced step, that lies between one
step's root span ``trainer.step`` and the next one's: the caller's (batch
making, the harness's bookkeeping). Between two host spans, so on the host's
clock alone. By ``step_span_ms_p50.gap_parts``."""

from harness.spec import load_module

UNIT = "ms"


def compute(record, trace):
    parts = load_module("layer_metrics", "step_span_ms_p50").gap_parts(record, trace)
    return None if parts is None else parts["caller"]
