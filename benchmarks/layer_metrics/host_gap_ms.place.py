"""The part of ``host_gap_ms.train``, per traced step, from the next step's
start to the start of its ``trainer.step.dispatch``: ``trainer.step.place``
(the batch and the mask put on the device) and the root span's own time
before it. Between two host spans, so on the host's clock alone. By
``step_span_ms_p50.gap_parts``."""

from harness.spec import load_module

UNIT = "ms"


def compute(record, trace):
    parts = load_module("layer_metrics", "step_span_ms_p50").gap_parts(record, trace)
    return None if parts is None else parts["place"]
