"""Long-context trainer: DP x SP over a (data, seq) mesh.

Composes the framework's two pillars in one jitted SPMD step:

- **sequence parallelism** along the ``seq`` axis — each device holds a
  (B_local, T_local) token shard; attention runs as ring attention (K/V
  rotating over ICI neighbors) or Ulysses all-to-all (ops/ring_attention.py);
- **threshold-masked gradient allreduce** along BOTH axes — the same
  contributor-mask semantics as the reference's threshold allreduce
  (SURVEY.md §8.1 step 3), with the mask applied per DP *replica row*: a
  dropped/straggling replica's v=0 zeroes its whole row's contribution while
  the collective still completes, exactly the reference's partial-completion
  round recast over a 2D mesh.

The reference itself has neither sequence parallelism nor transformers
(SURVEY.md §6); this is the TPU rebuild's long-context layer.

The step, the gradient collective and the host loop are
``train/sharded_lm.py``'s, shared with ``MoETrainer``; this file is what a
dense DP x SP x TP run adds to them.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from akka_allreduce_tpu.binder.api import flatten_pytree
from akka_allreduce_tpu.comm.allreduce import validate_trainer_compress
from akka_allreduce_tpu.train.checkpoint import place_on, state_shardings
from akka_allreduce_tpu.train.sharded_lm import ShardedLMTrainer, step_check_vma


@dataclasses.dataclass
class LongContextStepMetrics:
    step: int
    loss: float  # masked per-token average cross-entropy
    contributors: float  # contributing DP replica rows


class LongContextTrainer(ShardedLMTrainer):
    """DP x SP (x TP) trainer for a :class:`~akka_allreduce_tpu.models.TransformerLM`.

    Args:
      model_cls: the TransformerLM class (or compatible); instantiated here so
        ``seq_axis`` always matches the mesh.
      mesh: a 2-axis (data, seq) mesh from ``parallel.data_seq_mesh``, or a
        3-axis (data, seq, model) mesh from ``parallel.data_seq_model_mesh``
        — the third axis adds Megatron-style tensor parallelism: attention
        heads and MLP hidden shard over it (``models.transformer.tp_param_specs``),
        one psum per projection pair completes the partials, and gradients
        for sharded leaves stay shard-local (shard_map's autodiff psums them
        over data/seq only, because those leaves enter device-varying on
        ``model``).
      seq_len: GLOBAL sequence length (divisible by the seq axis size).
      seq_impl: "ring" or "ulysses".
    """

    metrics_cls = LongContextStepMetrics
    _mean_names = ("loss",)

    def __init__(
        self,
        mesh: Mesh,
        *,
        model_cls=None,
        vocab: int = 64,
        d_model: int = 64,
        n_heads: int = 4,
        n_kv_heads: int | None = None,
        n_layers: int = 2,
        seq_len: int = 128,
        seq_impl: str = "ring",
        optimizer: optax.GradientTransformation | None = None,
        learning_rate: float = 0.1,
        seed: int = 0,
        compute_dtype=jnp.float32,
        remat: bool = False,
        compress: str | None = None,
        overlap: bool = False,
    ) -> None:
        from akka_allreduce_tpu.models.transformer import (
            TransformerLM,
            tp_param_specs,
        )

        self.compress = validate_trainer_compress(compress, overlap=overlap)
        self.overlap = overlap

        if len(mesh.axis_names) not in (2, 3):
            raise ValueError(
                f"need a (data, seq[, model]) mesh, got axes {mesh.axis_names}"
            )
        self.mesh = mesh
        self.data_axis, self.seq_axis = mesh.axis_names[:2]
        self.model_axis = mesh.axis_names[2] if len(mesh.axis_names) == 3 else None
        self.dp = int(mesh.shape[self.data_axis])
        self.sp = int(mesh.shape[self.seq_axis])
        self.tp = (
            int(mesh.shape[self.model_axis]) if self.model_axis else 1
        )
        self.n_devices = self.dp * self.sp * self.tp
        self.data_shards = self.dp  # train_chain streams: one per replica row
        if seq_len % self.sp:
            raise ValueError(f"{seq_len=} not divisible by seq shards {self.sp}")
        self.seq_len = seq_len
        self.vocab = vocab
        cls = model_cls or TransformerLM
        self.model = cls(
            vocab=vocab,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv_heads,
            n_layers=n_layers,
            seq_axis=self.seq_axis,
            seq_impl=seq_impl,
            compute_dtype=compute_dtype,
            model_axis=self.model_axis if self.tp > 1 else None,
            tp_size=self.tp,
            remat=remat,
        )
        self.tx = optimizer or optax.adam(learning_rate)

        # init runs the module in single-device (dense, tp=1) form: FULL param
        # shapes. Under TP the shard_map in_specs slice each leaf to the
        # local geometry the tp_size>1 module declares.
        init_model = cls(
            vocab=vocab,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv_heads,
            n_layers=n_layers,
            compute_dtype=compute_dtype,
        )
        tokens0 = jnp.zeros((1, seq_len // self.sp), jnp.int32)
        self.params = init_model.init(jax.random.PRNGKey(seed), tokens0)
        self._place_state(
            (lambda tree: tp_param_specs(tree, self.model_axis))
            if self.tp > 1 else None
        )
        model_apply = self.model.apply

        def local_loss(p, x, y, tokens_local):
            logits = model_apply(p, x)  # (B_local, T_local, vocab)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).sum()
            return ce, ((ce,), ())

        self._build_step(
            local_loss,
            batch_spec=P(self.data_axis, self.seq_axis),
            check_vma=step_check_vma(
                seq_len=seq_len, head_dim=d_model // n_heads, sp=self.sp,
                seq_impl=seq_impl, compress=compress, overlap=overlap,
            ),
        )

    def set_flat_params(self, vec: np.ndarray) -> None:
        """Replace params from a flat float32 vector (binder/cluster seam),
        honoring the trainer's sharding layout (replicated or TP specs)."""
        # the tree structure never changes after __init__: build the
        # unflattener once, not one full device_get per sync round
        if getattr(self, "_unflatten", None) is None:
            _, self._unflatten = flatten_pytree(self.params)
        p_sh, _ = state_shardings(self)
        self.params = place_on(
            self._unflatten(np.asarray(vec, np.float32)), p_sh
        )
