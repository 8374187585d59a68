"""The multi-token-prediction module's share of a step's device time: the
ops of a trace whose HLO instruction carries the program's named scope
``mtp`` (the module's two norms, ``eh_proj``, its latent-attention + expert
layer, its final norm, its pass through the shared head and its
cross-entropy, forward and backward) over the time of all ops. The shared
embedding's and head's Adam updates are outside the scope."""

from harness.spec import load_module

UNIT = "%"
SCOPE = r"(?:^|/)mtp(?:/|$)"


def compute(record, trace):
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, trace, SCOPE
    )
    return None if found is None else 100.0 * found[0] / found[1]
