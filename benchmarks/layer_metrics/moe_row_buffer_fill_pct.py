"""How full the held experts' row buffer is: the rows routed to held
experts over the rows of the buffer the program moved and multiplied for
them, per expert layer and step, averaged over the window's units. The rows
routed are the program's counter (``MoEStepMetrics.expert_rows``, which the
runner puts into each unit's dict); the buffer is the smallest rung of the
program's own ladder (``ops.moe.row_rungs``, from the cell's shapes) that
holds them, which is the rung the program takes. A program without the
ladder reports nothing."""

UNIT = "%"


def compute(record, trace):
    try:
        from akka_allreduce_tpu.ops.moe import row_rungs
    except ImportError:
        return None
    units = [u.get("expert_rows") for u in record["window"]["units"]]
    cell = record["cell"]
    rungs = row_rungs(
        cell.traffic["batch"] * cell.traffic["seq_len"]
        * cell.config["num_experts_per_tok"],
        cell.config["num_experts"],
        cell.config.get("router_num_experts", cell.config["num_experts"]),
    )
    fills = [
        sum(layer) / next(r for r in rungs if r >= sum(layer))
        for u in units if u for layer in u
    ]
    return 100.0 * sum(fills) / len(fills) if fills else None
