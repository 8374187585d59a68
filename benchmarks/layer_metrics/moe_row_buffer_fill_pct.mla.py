"""How full the held experts' row buffer is, as ``moe_row_buffer_fill_pct``
reads it, for a runner whose units carry the buffer each layer took: the rows
routed to the held experts (``expert_rows``) over the rows of the rung the
program moved and multiplied for them (``buffer_rows``, both
``MoEStepMetrics`` counters), per expert layer (the prediction module's among
them) and step, averaged over the window's units. It shows a buffer that is
empty, or one that overflowed into the last rung: the load of this cut is not
stationary (PERF.md section 6, PR 32). Units without the counters report
nothing."""

UNIT = "%"


def compute(record, trace):
    fills = [
        sum(layer) / taken
        for u in record["window"]["units"]
        if u.get("expert_rows") and u.get("buffer_rows")
        for layer, taken in zip(u["expert_rows"], u["buffer_rows"])
    ]
    return 100.0 * sum(fills) / len(fills) if fills else None
