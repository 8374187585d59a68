"""The grouped-product kernels' share of their roofline, as
``moe_gmm_roofline_pct.mellum`` reads it, from the Qwen3-MoE keys that
``KeyeVL2``'s language model writes (``harness/keye_flops.grouped_products``):
per expert layer the larger of the products' FLOPs over the bf16 peak and
their least bytes (the rows', and the weights of the experts WITH a row) over
the HBM rate, over the kernels' device time. Rows, the experts that received
any and the row buffer each layer took are the program's counters, of the
units the trace covers (the window's first ``steps``). A layer that took a
larger buffer than the first multiplied through ``lax.ragged_dot``, not
through these kernels (``ops.moe._on_rung`` hands the kernels to the first
rung's body alone), so its time is not in ``moe_gmm_ms`` and its work is left
out here."""

from harness.keye_flops import grouped_products
from harness.spec import load_module

UNIT = "%"


def compute(record, trace):
    seconds = load_module("layer_metrics", "moe_gmm_ms").per_step_seconds(trace)
    steps = len(trace.main_module())
    units = [u for u in record["window"]["units"][:steps]
             if u.get("expert_rows") and u.get("buffer_rows")]
    if seconds is None or not units or "sa_config" not in record["cell"].config:
        return None
    first_rung = min(min(u["buffer_rows"]) for u in record["window"]["units"]
                     if u.get("buffer_rows"))
    peak, least = record["peak"], 0.0
    for u in units:
        for layer, taken in zip(u["expert_rows"], u["buffer_rows"]):
            if taken != first_rung:
                continue
            need = grouped_products(
                record["cell"].config, sum(layer), sum(1 for r in layer if r > 0))
            least += max(need["flops"] / peak["bf16_flops_per_s"],
                         need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / len(units) / seconds
