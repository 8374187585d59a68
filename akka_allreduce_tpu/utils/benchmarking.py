"""FLOP accounting / MFU for the train CLIs' summary print and ``soak.py``:
``PEAK_BF16_FLOPS``, ``device_peak_flops``, ``dense_train_flops``,
``transformer_train_flops``, ``mfu``, ``moe_active_params``.
"""

from __future__ import annotations

# VERDICT r2 #1: every workload reports model-FLOPs utilization, not just
# ms/step. Conventions (PaLM appendix B / Chinchilla):
#
# - model FLOPs are the THEORETICAL matmul work of one step: 2·N per token
#   forward, 4·N backward → 6·N·tokens, plus the attention score/value
#   matmuls which the parameter count does not see (12·B·T²·d per layer,
#   halved for causal kernels that skip the upper triangle);
# - rematerialization/recompute does NOT count toward MFU (that would be
#   HFU); pass remat=True only when the hardware-FLOPs view is wanted;
# - the denominator is the chip's dense bf16 MXU peak. f32 workloads are
#   measured against the same bf16 peak (conservative: the MXU's native
#   training dtype), with the compute dtype recorded alongside.

#: dense bf16 matmul peak FLOP/s by `jax.Device.device_kind`
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5": 459e12,  # v5p
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,  # v6e / Trillium
}


def device_peak_flops(device=None) -> float | None:
    """Dense bf16 MXU peak for ``device`` (default: jax.devices()[0]).

    Returns None off-TPU (CPU meshes have no meaningful MFU denominator).
    A TPU whose ``device_kind`` is missing from :data:`PEAK_BF16_FLOPS`
    raises, naming the kind: a silent ``None`` would drop the MFU from
    every record on exactly the machine it is measured on.
    """
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    if kind in PEAK_BF16_FLOPS:
        return PEAK_BF16_FLOPS[kind]
    if getattr(device, "platform", "") == "tpu":
        raise ValueError(
            f"no bf16 peak for TPU device_kind {kind!r}: add it to "
            "utils.benchmarking.PEAK_BF16_FLOPS (known: "
            f"{sorted(PEAK_BF16_FLOPS)})"
        )
    return None


def dense_train_flops(
    n_params: int | float, tokens: int | float, *, remat: bool = False
) -> float:
    """Model FLOPs of one training step of a dense (matmul-dominated) model:
    ``6·N·tokens`` (2N forward + 4N backward per token/sample).

    ``n_params`` approximates the matmul-participating parameter count with
    the total (embeddings/norms overcount by a sub-percent at real widths).
    ``remat=True`` adds one extra forward (8N — the HFU numerator).
    """
    per_token = 8.0 if remat else 6.0
    return per_token * float(n_params) * float(tokens)


def transformer_train_flops(
    *,
    n_params: int | float,
    batch: int,
    seq: int,
    d_model: int,
    n_layers: int,
    causal: bool = True,
    remat: bool = False,
) -> float:
    """Model FLOPs of one Transformer LM training step.

    Dense term ``6·N·B·T`` plus the attention score/value matmuls
    ``12·B·T²·d`` per layer (forward 4·B·T²·d, backward 2×), halved for
    causal attention (the flash kernel skips fully-masked blocks).
    ``remat=True`` adds one extra forward of both terms (HFU numerator).
    """
    # dense: 2N fwd + 4N bwd (+2N remat) per token
    n_forwards = 4.0 if remat else 3.0  # forward-equivalents in one step
    dense = 2.0 * float(n_params) * batch * seq * n_forwards
    # attention: fwd = 4·B·T²·d per layer (QKᵀ and AV, 2 FLOPs/MAC each),
    # halved causal; bwd = 2·fwd; remat adds another fwd
    attn = 4.0 * batch * float(seq) ** 2 * d_model * n_layers * n_forwards
    if causal:
        attn *= 0.5
    return dense + attn


def mfu(
    flops_per_step: float,
    seconds_per_step: float,
    peak_flops: float | None = None,
    *,
    n_devices: int = 1,
) -> float | None:
    """Model-FLOPs utilization in [0, 1]; None when no TPU peak applies.

    ``flops_per_step`` is the GLOBAL (whole-batch) model work, so the
    denominator is ``n_devices`` × the per-chip peak — pass the mesh's
    device count or a single chip's 40 % prints as n×40 %.
    """
    if peak_flops is None:
        peak_flops = device_peak_flops()
    if peak_flops is None or seconds_per_step <= 0:
        return None
    return flops_per_step / seconds_per_step / (peak_flops * n_devices)


def moe_active_params(
    params, topk: int, n_experts: int
) -> float:
    """ACTIVE parameter count of a Switch/GShard MoE params tree: each token
    runs ``topk`` of the ``n_experts`` expert MLPs, so the expert leaves
    (path contains ``moe_``) scale by topk/n_experts; everything else counts
    fully. Feed the result to :func:`transformer_train_flops` as
    ``n_params``."""
    import jax
    import numpy as np

    total = sum(
        int(np.prod(np.shape(leaf))) for leaf in jax.tree.leaves(params)
    )
    expert = sum(
        int(np.prod(np.shape(leaf)))
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if any("moe_" in str(getattr(k, "key", "")) for k in path)
    )
    return total - expert + expert * topk / n_experts
