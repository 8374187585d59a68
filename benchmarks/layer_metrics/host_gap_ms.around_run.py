"""The part of ``host_gap_ms.train``, per traced step, that lies around the
device's runs: from the end of a step's program to its ``train_step``'s
return (what is left of ``trainer.step.fetch``, the root span's own time after
it) and from the next step's ``trainer.step.dispatch`` to its program's start
on the device: the gap less ``host_gap_ms.caller`` and ``host_gap_ms.place``.
By ``step_span_ms_p50.gap_parts``, which says why its two sides are not told
apart."""

from harness.spec import load_module

UNIT = "ms"


def compute(record, trace):
    parts = load_module("layer_metrics", "step_span_ms_p50").gap_parts(record, trace)
    return None if parts is None else parts["around_run"]
