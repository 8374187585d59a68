"""Runner for cells that train a decoder LM with ``LongContextTrainer``,
one host-loop ``train_step`` after another, as ``train-lm`` does.

Set-up builds ONE trainer, gives it the seed's weights (made by the
reference's generator, handed in through the trainer's ``model_cls`` seam, so
the program's eager ``init`` never runs), drives it through its first steps
with the window's own call and feed, keeps what the check needs (each loss,
the first gradient's norm per leaf as Adam got it - its first moment after one
step from zero is (1 - b1) g - and the norm of the parameters' change), and
hands that same trainer to the window. After the window the trainer is freed
and the plain reference follows the same steps from the same seed.
"""

from __future__ import annotations

import gc
import math
import time

from harness import spec, traffic
from harness.stats import worst_leaf_gap

#: reference leaf -> path in the program's flax tree (under "params")
_LAYER = {
    "ln1.scale": ("LayerNorm_0", "scale"), "ln1.bias": ("LayerNorm_0", "bias"),
    "q.w": ("Attention_0", "q", "kernel"), "q.b": ("Attention_0", "q", "bias"),
    "k.w": ("Attention_0", "k", "kernel"), "k.b": ("Attention_0", "k", "bias"),
    "v.w": ("Attention_0", "v", "kernel"), "v.b": ("Attention_0", "v", "bias"),
    "o.w": ("Attention_0", "out", "kernel"), "o.b": ("Attention_0", "out_bias"),
    "ln2.scale": ("LayerNorm_1", "scale"), "ln2.bias": ("LayerNorm_1", "bias"),
    "fc.w": ("mlp_up", "kernel"), "fc.b": ("mlp_up", "bias"),
    "proj.w": ("mlp_down", "kernel"), "proj.b": ("mlp_bias",),
}
_TOP = {
    "embed": ("Embed_0", "embedding"),
    "ln_f.scale": ("LayerNorm_0", "scale"), "ln_f.bias": ("LayerNorm_0", "bias"),
    "head.w": ("Dense_0", "kernel"), "head.b": ("Dense_0", "bias"),
}


def program_path(name: str) -> tuple[str, ...]:
    if name.startswith("layers."):
        _, i, leaf = name.split(".", 2)
        return ("params", f"Block_{i}") + _LAYER[leaf]
    return ("params",) + _TOP[name]


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def by_reference_name(tree, names) -> dict:
    """The program's leaves under the reference's names (shapes as they are)."""
    return {n: _get(tree, program_path(n)) for n in names}


def to_program_tree(leaves: dict, cfg: dict) -> dict:
    """The reference's flat leaves in the program's tree and shapes: flax
    keeps heads as an axis of their own, (d, H, hd) and (H, hd, d)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = d // h
    tree: dict = {}
    for name, leaf in leaves.items():
        kind = name.rsplit(".", 2)[-2:]
        if kind[0] in ("q", "k", "v"):
            leaf = leaf.reshape((d, -1, hd) if kind[1] == "w" else (-1, hd))
        elif kind == ["o", "w"]:
            leaf = leaf.reshape(h, hd, d)
        node, path = tree, program_path(name)
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    return tree


def _leaf_norms(tree, names):
    import jax
    import jax.numpy as jnp

    picked = by_reference_name(tree, names)
    out = jax.jit(lambda t: {
        n: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
        for n, a in t.items()
    })(picked)
    return {n: float(v) for n, v in out.items()}


class Runner:
    spans = ("make_batch", "train_step")

    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.cfg = ctx.cell.config
        self.prog = self.cfg["program"]
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
        import jax.numpy as jnp
        import optax

        from akka_allreduce_tpu.models.transformer import TransformerLM
        from akka_allreduce_tpu.parallel import data_seq_mesh
        from akka_allreduce_tpu.train import LongContextTrainer

        cfg, prog = self.cfg, self.prog
        phases = {"import_program_s": time.perf_counter() - t}
        t = time.perf_counter()
        # popped by the one init call, so that nothing but the trainer holds them
        weights = [to_program_tree(self.ref.init_params(cfg, self.ctx.seed), cfg)]
        jax.block_until_ready(weights)
        phases["make_weights_s"] = time.perf_counter() - t

        class SeededLM:
            """``TransformerLM`` whose ``init`` hands out the seed's weights."""

            def __init__(self, **kw):
                self.apply = TransformerLM(**kw).apply

            def init(self, key, tokens):
                return weights.pop()

        if cfg["intermediate_size"] != 4 * cfg["hidden_size"]:
            raise ValueError("the program's Block has mlp_ratio 4 only")
        t = time.perf_counter()
        mesh = data_seq_mesh(prog["dp"], prog["sp"], devices=self.ctx.devices)
        self.trainer = LongContextTrainer(
            mesh, model_cls=SeededLM, vocab=cfg["vocab_size"],
            d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            n_layers=cfg["num_hidden_layers"], seq_len=self.traffic["seq_len"],
            optimizer=optax.adam(prog["learning_rate"], b1=prog["adam_b1"]),
            compute_dtype=jnp.dtype(prog["compute_dtype"]),
            remat=prog["remat"],
        )
        phases["build_trainer_s"] = time.perf_counter() - t
        assert self.trainer.param_count == sum(
            math.prod(s) for s in self.ref.param_shapes(cfg).values()
        ), "the program's tree and the reference's differ in size"

        # the first steps: through the window's own call and feed
        self.first = [self._batch(i) for i in range(3)]
        losses, step_s, t_first = [], [], time.perf_counter()
        for i, (x, y) in enumerate(self.first):
            t = time.perf_counter()
            losses.append(self.trainer.train_step(x, y).loss)
            step_s.append(time.perf_counter() - t)
            if i == 0:
                mu = next(s.mu for s in self.trainer.opt_state if hasattr(s, "mu"))
                scale = 1.0 - prog["adam_b1"]
                grad_norms = {
                    n: v / scale for n, v in _leaf_norms(mu, self.names).items()
                }
        phases["first_steps_s"] = step_s
        phases["first_steps_and_grad_norms_s"] = time.perf_counter() - t_first
        t = time.perf_counter()
        self.observed = {
            "losses": losses, "grad_norms": grad_norms,
            "delta_norms": self.ref.delta_norms(
                by_reference_name(self.trainer.params, self.names),
                cfg, self.ctx.seed,
            ),
        }
        phases["norms_s"] = time.perf_counter() - t
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
        ok = math.isfinite(m.loss) and m.contributors == self.trainer.dp
        self.last_loss = m.loss
        return {"work": self.batch[0].size, "ok": ok}

    def after_unit(self, i: int) -> None:
        pass

    def close_window(self) -> dict:
        return {"work_unit": "tokens", "last_loss": self.last_loss,
                "params": self.trainer.param_count}

    # -- the check -------------------------------------------------------------------

    def check(self) -> list[dict]:
        self.trainer = None  # its state goes; the reference needs the room
        gc.collect()
        ref = self.ref.follow(self.cfg, self.prog, self.ctx.seed, self.first)
        return compare(self.observed, ref, self.cfg["correct_limits"])


def compare(observed: dict, ref: dict, limits: dict) -> list[dict]:
    """Each number compared beside its limit."""
    values = {
        "loss_gap": max(
            abs(a - b) / abs(b) for a, b in zip(observed["losses"], ref["losses"])
        ),
        "grad_norm_gap": worst_leaf_gap(observed["grad_norms"], ref["grad_norms"]),
        "delta_norm_gap": worst_leaf_gap(observed["delta_norms"], ref["delta_norms"]),
    }
    out = [
        {"name": n, "value": v, "limit": limits[n],
         "ok": bool(math.isfinite(v) and v <= limits[n])}
        for n, v in values.items()
    ]
    out.append({"name": "losses", "program": observed["losses"],
                "reference": ref["losses"], "ok": True})
    return out
