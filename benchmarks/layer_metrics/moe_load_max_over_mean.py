"""Imbalance of the held experts' load: the fullest held expert's rows over
the mean of the held experts' rows, per expert layer and step, averaged over
the window's units. From the program's counter (``MoEStepMetrics.
expert_rows``, which the runner puts into each unit's dict)."""

UNIT = "x"


def _units(record):
    units = [u.get("expert_rows") for u in record["window"]["units"]]
    return [u for u in units if u]


def routed_rows(record):
    """Mean rows per step routed to held experts, one number per expert
    layer; None where the program counts none."""
    units = _units(record)
    if not units:
        return None
    layers = len(units[0])
    return [sum(sum(u[j]) for u in units) / len(units) for j in range(layers)]


def compute(record, trace):
    ratios = [
        max(layer) * len(layer) / sum(layer)
        for u in _units(record) for layer in u if sum(layer) > 0
    ]
    return sum(ratios) / len(ratios) if ratios else None
