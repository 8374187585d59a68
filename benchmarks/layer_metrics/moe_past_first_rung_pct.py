"""The share of the window's (expert layer, step) pairs whose row buffer was
not the ladder's first rung: the program's counter
``trainer.moe.layers_past_first_rung`` over expert layers times
``trainer.steps``, both as they moved over the window (the runner hands the
counters' change with the first unit, ``counters``). Such a layer moved and
multiplied a larger rung's rows (``ops.moe.row_rungs``: 65,536 against 20,480
at the Mellum2 cell) through ``lax.ragged_dot``, not through the kernels. A
program without the counters reports nothing."""

UNIT = "%"


def window_counters(record) -> dict:
    return next(
        (u["counters"] for u in record["window"]["units"] if u.get("counters")), {}
    )


def compute(record, trace):
    moved = window_counters(record)
    steps = moved.get("trainer.steps")
    if not steps or "trainer.moe.layers_past_first_rung" not in moved:
        return None
    layers = len(next(
        u["buffer_rows"] for u in record["window"]["units"] if u.get("buffer_rows")
    ))
    return 100.0 * moved["trainer.moe.layers_past_first_rung"] / (layers * steps)
