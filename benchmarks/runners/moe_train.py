"""Runner for cells that train a configuration-built decoder with held
experts (``models.hybrid_decoder``) through ``MoETrainer``, one host-loop
``train_step`` after another, as ``train-moe --config`` does.

As ``lm_train``: set-up builds ONE trainer, hands it the seed's weights and
selection bias (made by the reference's generators, through the trainer's
``model=`` / ``params=`` seam, so no ``init`` runs), drives it through its
first steps with the window's own call and feed, keeps what the check needs
(each loss, the first gradient's norm per leaf as Adam got it, the norm of
the parameters' change) and hands that same trainer to the window. Each
unit's dict carries the rows every held expert received; a unit that
dropped an assignment counts as failed. After the window the trainer is
freed and the plain reference follows the same steps from the same seed.
"""

from __future__ import annotations

import gc
import math
import time

from harness import spec, traffic

#: reference leaf (after ``layers.<i>.``) -> path under ``layers_<i>_...``
_LAYER = {
    "op_norm.scale": ("op_norm", "scale"), "ffn_norm.scale": ("ffn_norm", "scale"),
    "conv.in.w": ("conv", "in_proj", "kernel"), "conv.filter": ("conv", "conv"),
    "conv.out.w": ("conv", "out_proj", "kernel"),
    "q.w": ("attn", "q", "kernel"), "k.w": ("attn", "k", "kernel"),
    "v.w": ("attn", "v", "kernel"), "o.w": ("attn", "out", "kernel"),
    "q_norm.scale": ("attn", "q_norm", "scale"),
    "k_norm.scale": ("attn", "k_norm", "scale"),
    "mlp.w1": ("mlp", "w1", "kernel"), "mlp.w3": ("mlp", "w3", "kernel"),
    "mlp.w2": ("mlp", "w2", "kernel"),
    "router.w": ("moe", "router"), "experts.w1": ("moe", "w1"),
    "experts.w3": ("moe", "w3"), "experts.w2": ("moe", "w2"),
}
_TOP = {
    "embed": ("embed", "embedding"), "final_norm.scale": ("final_norm", "scale"),
    "head.w": ("head",),
}


def program_path(name: str) -> tuple[str, ...]:
    if name.startswith("layers."):
        _, i, leaf = name.split(".", 2)
        module, *rest = _LAYER[leaf]
        return ("params", f"layers_{i}_{module}", *rest)
    return ("params",) + _TOP[name]


def by_reference_name(tree, names) -> dict:
    """The program's leaves under the reference's names (same shapes)."""
    out = {}
    for n in names:
        node = tree
        for key in program_path(n):
            node = node[key]
        out[n] = node
    return out


def to_program_tree(leaves: dict, select_bias, cfg: dict) -> dict:
    """The reference's flat leaves and its (expert layers, E) selection bias
    as the program's variables: ``params`` and the ``fixed`` collection."""
    tree: dict = {"params": {}}
    for name, leaf in leaves.items():
        node, path = tree, program_path(name)
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    if cfg["use_expert_bias"]:
        first = cfg["num_dense_layers"]
        tree["fixed"] = {
            f"layers_{first + j}_moe": {"select_bias": select_bias[j]}
            for j in range(cfg["num_hidden_layers"] - first)
        }
    return tree


def build_model(cfg: dict):
    import jax.numpy as jnp

    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM

    return HybridDecoderLM.from_config(
        cfg, compute_dtype=jnp.dtype(cfg["program"]["compute_dtype"])
    )


def build_trainer(cfg: dict, seq_len: int, variables, devices):
    import jax
    import optax

    from akka_allreduce_tpu.train import MoETrainer

    prog = cfg["program"]
    mesh = jax.make_mesh((prog["dp"],), ("data",), devices=devices[: prog["dp"]])
    return MoETrainer(
        mesh, model=build_model(cfg), params=variables,
        vocab=cfg["vocab_size"], seq_len=seq_len,
        optimizer=optax.adam(prog["learning_rate"], b1=prog["adam_b1"]),
    )


def lower_step_on_shapes(cfg: dict, traffic_cfg: dict, device):
    """``(trainer, lowered step)`` with shapes in place of arrays, for a
    device that is described and not attached (the rehearsals that compile
    the cell's step at real size without a chip): no weight is made, and
    nothing is placed."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding

    from akka_allreduce_tpu.train import MoETrainer

    prog, model = cfg["program"], build_model(cfg)
    shape = (traffic_cfg["batch"], traffic_cfg["seq_len"])
    adam = optax.adam(prog["learning_rate"], b1=prog["adam_b1"])
    mesh = jax.make_mesh((1,), ("data",), devices=[device])
    with mock.patch.object(jax, "device_put", lambda x, *a, **k: x):
        trainer = MoETrainer(
            mesh, model=model, vocab=cfg["vocab_size"], seq_len=shape[1],
            params=jax.eval_shape(
                model.init, jax.random.PRNGKey(0), jnp.zeros(shape, jnp.int32)
            ),
            optimizer=optax.GradientTransformation(
                lambda p: jax.eval_shape(adam.init, p), adam.update
            ),
        )

    def shapes(tree, specs):
        return jax.tree.map(
            lambda leaf, s: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, s)
            ),
            tree, specs,
        )

    tokens = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=trainer._data_sharding)
    valid = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=trainer._valid_sharding)
    return trainer, trainer._step.lower(
        shapes(trainer.params, trainer._param_specs),
        shapes(trainer.opt_state, trainer._opt_specs), tokens, tokens, valid,
    )


def leaf_norms(tree, names) -> dict:
    import jax
    import jax.numpy as jnp

    out = jax.jit(lambda t: {
        n: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
        for n, a in t.items()
    })(by_reference_name(tree, names))
    return {n: float(v) for n, v in out.items()}


def first_steps(trainer, ref, cfg: dict, seed: int, batches, names) -> dict:
    """Drive ``trainer`` through ``batches`` and keep what the check needs."""
    losses, grad_norms = [], None
    for x, y in batches:
        m = trainer.train_step(x, y)
        losses.append(m.loss)
        if grad_norms is None:
            # Adam's first moment after one step from zero is (1 - b1) g
            mu = next(s.mu for s in trainer.opt_state if hasattr(s, "mu"))
            scale = 1.0 - cfg["program"]["adam_b1"]
            grad_norms = {n: v / scale for n, v in leaf_norms(mu, names).items()}
    return {
        "losses": losses, "grad_norms": grad_norms,
        "delta_norms": ref.delta_norms(
            by_reference_name(trainer.params, names), cfg, seed
        ),
    }


class Runner:
    spans = ("make_batch", "train_step")

    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.cfg = ctx.cell.config
        self.traffic = ctx.cell.traffic
        self.ref = spec.load_module("reference", self.cfg["reference"])
        self.names = list(self.ref.param_shapes(self.cfg))
        self.trainer = None
        self.observed: dict = {}

    def _batch(self, unit: int):
        return traffic.token_batch(
            self.traffic, self.cfg["vocab_size"], self.ctx.seed, unit
        )

    # -- set-up ------------------------------------------------------------------

    def setup(self) -> dict:
        t = time.perf_counter()
        import jax

        # first, so that a program without the model fails before any weight is made
        import akka_allreduce_tpu.models.hybrid_decoder  # noqa: F401
        import akka_allreduce_tpu.train  # noqa: F401  (timed: the package's imports)

        cfg = self.cfg
        phases = {"import_program_s": time.perf_counter() - t}
        t = time.perf_counter()
        variables = to_program_tree(
            self.ref.init_params(cfg, self.ctx.seed),
            self.ref.select_bias(cfg, self.ctx.seed), cfg,
        )
        jax.block_until_ready(variables)
        phases["make_weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        self.trainer = build_trainer(
            cfg, self.traffic["seq_len"], variables, self.ctx.devices
        )
        del variables  # nothing but the trainer holds them
        phases["build_trainer_s"] = time.perf_counter() - t
        n_bias = math.prod(self.ref.select_bias(cfg, 0).shape) * cfg["use_expert_bias"]
        assert self.trainer.param_count - n_bias == sum(
            math.prod(s) for s in self.ref.param_shapes(cfg).values()
        ), "the program's tree and the reference's differ in size"

        # the first steps: through the window's own call and feed
        self.first = [self._batch(i) for i in range(3)]
        t = time.perf_counter()
        self.observed = first_steps(
            self.trainer, self.ref, cfg, self.ctx.seed, self.first, self.names
        )
        phases["first_steps_and_norms_s"] = time.perf_counter() - t
        self.warm = max(3, int(self.traffic["warmup_units"]))
        for i in range(3, self.warm):
            self.trainer.train_step(*self._batch(i))
        return phases

    # -- the window ----------------------------------------------------------------

    def prepare(self, i: int) -> None:
        with self.ctx.span("make_batch"):
            self.batch = self._batch(self.warm + i)

    def unit(self, i: int) -> dict:
        with self.ctx.span("train_step"):
            m = self.trainer.train_step(*self.batch)
        ok = (
            math.isfinite(m.loss) and m.contributors == self.trainer.dp
            and m.dropped == 0
        )
        self.last = m
        return {"work": self.batch[0].size, "ok": ok, "dropped": m.dropped,
                "expert_rows": m.expert_rows.tolist()}

    def after_unit(self, i: int) -> None:
        pass

    def close_window(self) -> dict:
        return {"work_unit": "tokens", "last_loss": self.last.loss,
                "last_expert_rows": self.last.expert_rows.tolist(),
                "params": self.trainer.param_count}

    # -- the check -------------------------------------------------------------------

    def check(self) -> list[dict]:
        self.trainer = None  # its state goes; the reference needs the room
        gc.collect()
        ref = self.ref.follow(self.cfg, self.cfg["program"], self.ctx.seed, self.first)
        compare = spec.load_module("runners", "lm_train").compare
        return compare(self.observed, ref, self.cfg["correct_limits"])
