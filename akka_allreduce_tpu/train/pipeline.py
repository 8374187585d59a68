"""Pipeline-parallel Transformer training: DP x PP over a (data, pipe) mesh.

Beyond-parity capability (the reference is DP-only, SURVEY.md §3) and the
last of the classic strategies (DP/SP/TP/EP/ZeRO elsewhere in train/). Built
the TPU way — no host scheduler, no per-stage processes: the WHOLE pipeline
is one jitted SPMD program.

- The transformer trunk's L layers stack into one params tree with a leading
  layer dim, sharded ``P('pipe')``: each of the S stages holds L/S layers and
  runs them with a local ``lax.scan``.
- GPipe-style execution (``schedule="gpipe"``) is a second ``lax.scan``
  over ``M + S - 1`` ticks: every tick each stage applies its layers and
  hands its activation to the next stage with ONE ``ppermute`` hop over the
  ``pipe`` axis (neighbor traffic on the ICI torus). Stage 0 injects a
  fresh microbatch per tick; the last stage peels off finished microbatches
  and accumulates the loss. The (S-1)/(M+S-1) bubble is the standard GPipe
  trade.
- Autodiff differentiates straight through both scans: the reverse pass IS
  backward pipelining (cotangents ride the reverse ppermute), trunk
  gradients stay stage-local (the leaves enter shard_map device-varying on
  ``pipe``), and the replicated embed/head gradients are completed by the
  same transpose-psum mechanism as every other trainer here. The memory
  cost of that elegance: the scan saves every tick's carry for the reverse
  pass, so each stage holds O(M) in-flight microbatch activations.
- ``schedule="1f1b"`` (VERDICT r3 #4) hand-schedules forward AND backward
  in one scan over ``M + 2S - 2`` ticks, so memory is O(S) instead of
  O(M): forwards flow exactly like GPipe (micro f runs on stage s at tick
  ``s + f``), while micro b's backward runs on stage s at tick
  ``2(S-1) - s + b`` — the LAST stage backs up micro b in the same tick
  that forwarded it, and cotangents hop one stage per tick on the reverse
  ppermute. Each stage keeps only a ``2S - 1``-slot ring of pending stage
  INPUTS (the static proof of the O(S) bound: the scan carry IS the live
  state — no AD runs over the tick loop) and recomputes the stage forward
  inside its backward tick's ``jax.vjp`` (the remat trade built in).
  Gradients are accumulated per tick and completed by ONE explicit grouped
  collective per sharding class (``comm.allreduce.grouped_tree_psum`` —
  bf16/int8 wire compression compose unchanged); numerics match GPipe to
  float reassociation (same per-micro terms, summed in tick order instead
  of reverse-AD order).
- Threshold masking: the contributor mask is per DP replica row, exactly as
  in DPTrainer/LongContextTrainer — a dropped row zeroes its contribution
  while the collective completes.

Numerics are EXACT vs the unpipelined model (microbatching only reorders the
same sums), which is what the tests assert.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from akka_allreduce_tpu.comm.allreduce import synced_value_and_grad
from akka_allreduce_tpu.train.sharded_lm import step_check_vma


@dataclasses.dataclass
class PipelineStepMetrics:
    step: int
    loss: float  # masked per-token cross-entropy
    contributors: float  # contributing DP replica rows


class _LMHead(nn.Module):
    vocab: int
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(dtype=self.compute_dtype)(x)
        return nn.Dense(self.vocab, dtype=self.compute_dtype)(x).astype(
            jnp.float32
        )


class PipelineLMTrainer:
    """DP x PP trainer for a decoder-only Transformer LM.

    Args:
      mesh: a (data, pipe) 2-axis mesh (``pipe`` may be 1 = no pipelining,
        which is also the oracle the tests compare against).
      layers_per_stage: trunk depth per pipeline stage (total layers =
        layers_per_stage * pipe).
      microbatches: GPipe microbatches per step; the per-device batch must
        divide by it. More microbatches = smaller bubble, smaller matmuls.
    """

    @staticmethod
    def validate_flags(
        *,
        schedule: str = "gpipe",
        virtual_chunks: int = 1,
        layers_per_stage: int = 1,
        overlap: bool = False,
    ) -> None:
        """Raise ValueError for schedule/virtual/overlap combinations the
        trainer cannot run. Pure flag checks (no mesh/model state) so CLIs
        can convert them to usage errors BEFORE construction — one source
        of truth instead of hand-copied checks."""
        if schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(
                f"schedule must be gpipe, 1f1b or interleaved, got {schedule!r}"
            )
        if schedule in ("1f1b", "interleaved") and overlap:
            raise ValueError(
                "overlap excludes the hand-scheduled pipelines: their "
                "gradients are accumulated per tick (no backward pass for "
                "the per-leaf sync to hook); the grouped collective already "
                "fires once at the end of the tick scan"
            )
        if schedule == "interleaved":
            if virtual_chunks < 2:
                raise ValueError(
                    "schedule='interleaved' needs virtual_chunks >= 2 "
                    "(1 chunk IS plain 1f1b — use schedule='1f1b')"
                )
            if layers_per_stage % virtual_chunks:
                raise ValueError(
                    f"{layers_per_stage=} not divisible by "
                    f"{virtual_chunks=} chunks"
                )
        elif virtual_chunks != 1:
            raise ValueError(
                f"virtual_chunks={virtual_chunks} only applies to "
                "schedule='interleaved'"
            )

    def __init__(
        self,
        mesh: Mesh,
        *,
        vocab: int = 64,
        d_model: int = 64,
        n_heads: int = 4,
        n_kv_heads: int | None = None,
        layers_per_stage: int = 1,
        microbatches: int = 2,
        seq_len: int = 64,
        optimizer: optax.GradientTransformation | None = None,
        learning_rate: float = 1e-2,
        seed: int = 0,
        compute_dtype=jnp.float32,
        remat: bool = False,
        compress: str | None = None,
        overlap: bool = False,
        schedule: str = "gpipe",
        virtual_chunks: int = 1,
    ) -> None:
        from akka_allreduce_tpu.models.transformer import Block

        if len(mesh.axis_names) != 2:
            raise ValueError(
                f"need a (data, pipe) mesh, got axes {mesh.axis_names}"
            )
        self.validate_flags(
            schedule=schedule,
            virtual_chunks=virtual_chunks,
            layers_per_stage=layers_per_stage,
            overlap=overlap,
        )
        from akka_allreduce_tpu.comm.allreduce import validate_trainer_compress

        self.compress = validate_trainer_compress(compress, overlap=overlap)
        self.overlap = overlap
        self.schedule = schedule
        self.mesh = mesh
        self.data_axis, self.pipe_axis = mesh.axis_names
        self.dp = int(mesh.shape[self.data_axis])
        self.stages = int(mesh.shape[self.pipe_axis])
        self.n_devices = self.dp * self.stages
        self.microbatches = microbatches
        self.seq_len = seq_len
        self.vocab = vocab
        self.n_layers = layers_per_stage * self.stages
        self.tx = optimizer or optax.adam(learning_rate)

        block = Block(
            n_heads=n_heads, n_kv_heads=n_kv_heads,
            compute_dtype=compute_dtype,
        )
        embed = nn.Embed(vocab, d_model, dtype=compute_dtype)
        head = _LMHead(vocab, compute_dtype=compute_dtype)
        rng = jax.random.PRNGKey(seed)
        x0 = jnp.zeros((1, seq_len, d_model), jnp.float32)
        tok0 = jnp.zeros((1, seq_len), jnp.int32)
        layer_ps = [
            block.init(jax.random.fold_in(rng, 1000 + i), x0)["params"]
            for i in range(self.n_layers)
        ]
        # stack to (L, ...) leaves: ONE trunk tree, layer dim sharded on
        # pipe. Interleaved: stage s's local rows hold its v chunks in
        # chunk order, and chunk c of stage s is the LOGICAL block c*S + s
        # (a microbatch loops the ring v times, visiting blocks in logical
        # order), so the stacked row s*lps + c*cl + j carries logical
        # layer (c*S + s)*cl + j. _layer_perm maps stacked -> logical;
        # everything external (get_flat_params, checkpoints) sees logical.
        lps = layers_per_stage
        cl = lps // virtual_chunks
        self._layer_perm = np.arange(self.n_layers)
        if schedule == "interleaved":
            self._layer_perm = np.array(
                [
                    (c * self.stages + s) * cl + j
                    for s in range(self.stages)
                    for c in range(virtual_chunks)
                    for j in range(cl)
                ]
            )
        self._layer_perm_inv = np.argsort(self._layer_perm)
        trunk = jax.tree.map(
            lambda *ls: jnp.stack([ls[g] for g in self._layer_perm]),
            *layer_ps,
        )
        self.virtual_chunks = virtual_chunks
        self.params = {
            "embed": embed.init(jax.random.fold_in(rng, 1), tok0)["params"],
            "trunk": trunk,
            "head": head.init(jax.random.fold_in(rng, 2), x0)["params"],
        }
        self.opt_state = self.tx.init(self.params)
        self.param_count = int(
            sum(np.prod(p.shape) for p in jax.tree.leaves(self.params))
        )
        self.step_num = 0

        # one rule for params AND optax moments: any leaf whose path passes
        # through 'trunk' shards its leading (layer) dim on the pipe axis
        def stage_spec(path, leaf):
            names = [
                str(getattr(k, "key", getattr(k, "name", k))) for k in path
            ]
            if "trunk" in names:
                return P(*([self.pipe_axis] + [None] * (leaf.ndim - 1)))
            return P()

        self._param_specs = jax.tree_util.tree_map_with_path(
            stage_spec, self.params
        )
        self._opt_specs = jax.tree_util.tree_map_with_path(
            stage_spec, self.opt_state
        )
        is_spec = lambda x: isinstance(x, P)  # noqa: E731
        self.params = jax.device_put(
            self.params,
            jax.tree.map(
                lambda s: NamedSharding(mesh, s), self._param_specs,
                is_leaf=is_spec,
            ),
        )
        self.opt_state = jax.device_put(
            self.opt_state,
            jax.tree.map(
                lambda s: NamedSharding(mesh, s), self._opt_specs,
                is_leaf=is_spec,
            ),
        )

        axis_names = tuple(mesh.axis_names)
        data_axis, pipe_axis = self.data_axis, self.pipe_axis
        s_count = self.stages
        m_count = microbatches
        tx = self.tx
        param_specs = self._param_specs
        block_apply = block.apply
        embed_apply = embed.apply
        head_apply = head.apply

        def run_stage(trunk_local, h):
            """Apply this stage's layers_per_stage blocks sequentially;
            with ``remat`` each layer recomputes on backward (jax.checkpoint)
            so a stage holds one layer's activations, not layers_per_stage —
            the memory knob for deep stages and long sequences."""

            def body(carry, layer_p):
                return block_apply({"params": layer_p}, carry), None

            if remat:
                body = jax.checkpoint(body)
            out, _ = lax.scan(body, h, trunk_local)
            return out

        fwd = [(i, (i + 1) % s_count) for i in range(s_count)]

        def stage_context(x, valid):
            """The prologue BOTH schedules share — any change to masking or
            the loss denominator lands in one place, preserving the tested
            GPipe/1F1B equivalence by construction."""
            s = lax.axis_index(pipe_axis)
            v0 = valid.reshape(())
            v = lax.pcast(v0, pipe_axis, to="varying")
            b_local, t_len = x.shape
            if b_local % m_count:
                raise ValueError(
                    f"per-device batch {b_local} not divisible by "
                    f"{m_count} microbatches"
                )
            mb = b_local // m_count
            is_last = s == s_count - 1
            # only the last stage carries loss tokens; no double counting
            denom = jnp.maximum(
                lax.psum(
                    v
                    * jnp.float32(b_local * t_len)
                    * is_last.astype(jnp.float32),
                    axis_names,
                ),
                1.0,
            )
            return s, v0, v, mb, t_len, is_last, denom

        def apply_update(params, opt_state, gavg):
            updates, new_opt = tx.update(gavg, opt_state, params)
            return optax.apply_updates(params, updates), new_opt

        def stage_all(trunk_local, head_p, inp, lbl):
            """One stage's (or chunk's) whole tick-work: blocks, then
            head+loss. The single vjp point for BOTH hand-scheduled
            cotangent paths — mid stages seed d(out) with the received
            cotangent (d(ce)=0, so the head contributes nothing), the last
            stage seeds d(ce)=1. Shared by 1f1b and interleaved so the
            schedules can never diverge in per-tick math."""
            out = run_stage(trunk_local, inp)
            logits = head_apply({"params": head_p}, out)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, lbl
            ).sum()
            return out, ce

        def hand_epilogue(
            params, opt_state, g_emb, g_trunk, g_head, ce_total, v, v0, denom
        ):
            """Shared tail of the hand-scheduled schedules: mask-scale the
            accumulated grads, ONE grouped collective per sharding class
            (bf16/int8 wire compression composes here), loss psum, update."""
            grads = {"embed": g_emb, "trunk": g_trunk, "head": g_head}
            scale = v / denom
            grads = jax.tree.map(
                lambda g: g * scale.astype(g.dtype), grads
            )
            from akka_allreduce_tpu.comm.allreduce import grouped_tree_psum

            gavg = grouped_tree_psum(
                grads, param_specs, axis_names, wire_dtype=compress
            )
            loss_avg = lax.psum(ce_total * v / denom, axis_names)
            contributors = lax.psum(v0, data_axis)
            new_params, new_opt = apply_update(params, opt_state, gavg)
            return new_params, new_opt, loss_avg, contributors

        def step(params, opt_state, x, y, valid):
            s, v0, v, mb, t_len, is_last_b, denom = stage_context(x, valid)
            is_last = is_last_b.astype(jnp.float32)

            def pipeline_ce(p):
                """The GPipe forward: this device's summed loss tokens
                (nonzero only on the last stage's real microbatches)."""
                xe = embed_apply({"params": p["embed"]}, x)
                micro = xe.reshape(m_count, mb, t_len, -1)
                labels = y.reshape(m_count, mb, t_len)

                def tick(carry, t):
                    received = carry
                    # stage 0 injects microbatch t (clamped; ticks past M
                    # feed garbage that exits after the loop ends)
                    inj = lax.dynamic_index_in_dim(
                        micro, jnp.clip(t, 0, m_count - 1), 0, keepdims=False
                    )
                    inp = jnp.where(s == 0, inj, received)
                    out = run_stage(p["trunk"], inp)
                    nxt = lax.ppermute(out, pipe_axis, fwd)
                    # last stage peels microbatch m = t - (S-1) when it is real
                    m = t - (s_count - 1)
                    logits = head_apply({"params": p["head"]}, out)
                    lbl = lax.dynamic_index_in_dim(
                        labels, jnp.clip(m, 0, m_count - 1), 0, keepdims=False
                    )
                    ce = optax.softmax_cross_entropy_with_integer_labels(
                        logits, lbl
                    ).sum()
                    take = ((s == s_count - 1) & (m >= 0)).astype(jnp.float32)
                    return nxt, ce * take

                zero = jnp.zeros((mb, t_len, xe.shape[-1]), xe.dtype)
                # the carry becomes device-varying after its first ppermute
                # hop; the initial value must carry the same vma type
                zero = lax.pcast(zero, axis_names, to="varying")
                _, ces = lax.scan(
                    tick, zero, jnp.arange(m_count + s_count - 1)
                )
                return ces.sum()

            def loss_fn(p):
                ce_total = pipeline_ce(p)
                return ce_total / denom, ce_total

            # trunk leaves (pipe-sharded) reduce over data only, embed/head
            # over data x pipe
            (_, ce_total), gavg = synced_value_and_grad(
                loss_fn, params, param_specs, axis_names, v,
                compress=compress, overlap=overlap, has_aux=True,
            )
            loss_avg = lax.psum(ce_total * v * is_last / denom, axis_names)
            contributors = lax.psum(v0, data_axis)
            new_params, new_opt = apply_update(params, opt_state, gavg)
            return new_params, new_opt, loss_avg, contributors

        rev = [(i, (i - 1) % s_count) for i in range(s_count)]
        # max pending stage inputs under the 1f1b schedule: stage s holds
        # 2*(S-1-s) + 1 in-flight microbatches (forwards outpace backwards
        # by exactly the cotangent round trip) — bounded by 2S-1, O(S) and
        # M-independent. This ring IS the schedule's memory bound: no AD
        # runs over the tick scan, so the carry is the whole live state.
        ring_k = 2 * s_count - 1

        def step_1f1b(params, opt_state, x, y, valid):
            s, v0, v, mb, t_len, is_last, denom = stage_context(x, valid)
            micro_tok = x.reshape(m_count, mb, t_len)
            labels = y.reshape(m_count, mb, t_len)

            def tick(carry, t):
                ring, act_rx, ct_rx, g_emb, g_trunk, g_head, ce_acc = carry
                # ---- forward: micro f = t - s (GPipe pacing) ----
                f = t - s
                do_f = (f >= 0) & (f < m_count)
                fc = jnp.clip(f, 0, m_count - 1)
                tok_f = lax.dynamic_index_in_dim(
                    micro_tok, fc, 0, keepdims=False
                )
                lbl_f = lax.dynamic_index_in_dim(
                    labels, fc, 0, keepdims=False
                )
                emb_f = embed_apply({"params": params["embed"]}, tok_f)
                inp = jnp.where(s == 0, emb_f, act_rx)
                slot_f = jnp.mod(fc, ring_k)
                prev = lax.dynamic_slice_in_dim(ring, slot_f, 1, axis=0)[0]
                ring = lax.dynamic_update_slice_in_dim(
                    ring, jnp.where(do_f, inp, prev)[None], slot_f, axis=0
                )
                out_f, ce_f = stage_all(
                    params["trunk"], params["head"], inp, lbl_f
                )
                send = lax.ppermute(out_f, pipe_axis, fwd)
                ce_acc = ce_acc + ce_f * (
                    is_last & do_f
                ).astype(jnp.float32)

                # ---- backward: micro b = t - 2(S-1) + s ----
                b = t - 2 * (s_count - 1) + s
                do_b = (b >= 0) & (b < m_count)
                do_bf = do_b.astype(jnp.float32)
                bc = jnp.clip(b, 0, m_count - 1)
                slot_b = jnp.mod(bc, ring_k)
                inp_b = lax.dynamic_slice_in_dim(ring, slot_b, 1, axis=0)[0]
                tok_b = lax.dynamic_index_in_dim(
                    micro_tok, bc, 0, keepdims=False
                )
                lbl_b = lax.dynamic_index_in_dim(
                    labels, bc, 0, keepdims=False
                )
                (out_b, _), vjp_fn = jax.vjp(
                    lambda tr, hp, i: stage_all(tr, hp, i, lbl_b),
                    params["trunk"],
                    params["head"],
                    inp_b,
                )
                ct_out = (
                    jnp.where(is_last, jnp.zeros_like(out_b), ct_rx)
                    * do_bf.astype(out_b.dtype)
                )
                ct_ce = is_last.astype(jnp.float32) * do_bf
                d_trunk, d_head, d_inp = vjp_fn((ct_out, ct_ce))
                # stage 0's d(input) is the embedding cotangent; everyone
                # else forwards it down the reverse ring
                d_emb_ct = jnp.where(s == 0, d_inp, jnp.zeros_like(d_inp))
                _, evjp = jax.vjp(
                    lambda ep: embed_apply({"params": ep}, tok_b),
                    params["embed"],
                )
                (d_embp,) = evjp(d_emb_ct)
                g_emb = jax.tree.map(jnp.add, g_emb, d_embp)
                g_trunk = jax.tree.map(jnp.add, g_trunk, d_trunk)
                g_head = jax.tree.map(jnp.add, g_head, d_head)
                ct_send = lax.ppermute(d_inp, pipe_axis, rev)
                return (
                    ring, send, ct_send, g_emb, g_trunk, g_head, ce_acc,
                ), None

            act_dtype = jnp.dtype(compute_dtype)
            d_dim = d_model
            zeros_act = lax.pcast(
                jnp.zeros((mb, t_len, d_dim), act_dtype),
                axis_names,
                to="varying",
            )
            g0 = jax.tree.map(
                lambda p: lax.pcast(
                    jnp.zeros_like(p), axis_names, to="varying"
                ),
                params,
            )
            carry0 = (
                lax.pcast(
                    jnp.zeros((ring_k, mb, t_len, d_dim), act_dtype),
                    axis_names,
                    to="varying",
                ),
                zeros_act,
                zeros_act,
                g0["embed"],
                g0["trunk"],
                g0["head"],
                lax.pcast(jnp.float32(0.0), axis_names, to="varying"),
            )
            (_, _, _, g_emb, g_trunk, g_head, ce_total), _ = lax.scan(
                tick, carry0, jnp.arange(m_count + 2 * s_count - 2)
            )
            return hand_epilogue(
                params, opt_state, g_emb, g_trunk, g_head, ce_total,
                v, v0, denom,
            )

        # ---- interleaved 1F1B: v virtual chunks per stage, table-driven ----
        # (pipeline_schedule.py derives per-tick work tables and PROVES the
        # single sticky rx slot per direction suffices; the cyclic ppermute
        # wrap carries a microbatch from chunk c on stage S-1 to chunk c+1
        # on stage 0, so one scan serves all v loops around the ring)
        if schedule == "interleaved":
            from akka_allreduce_tpu.train.pipeline_schedule import (
                interleaved_1f1b_tables,
            )

            tabs = interleaved_1f1b_tables(s_count, m_count, virtual_chunks)
            self.schedule_tables = tabs
            tick_xs = (
                jnp.asarray(tabs.f_micro),
                jnp.asarray(tabs.f_chunk),
                jnp.asarray(tabs.f_arrive),
                jnp.asarray(tabs.b_micro),
                jnp.asarray(tabs.b_chunk),
                jnp.asarray(tabs.b_arrive),
            )
            rk = tabs.ring_k
        v_chunks = virtual_chunks
        chunk_l = layers_per_stage // virtual_chunks

        def chunk_slice(tree, c):
            """This stage's chunk c: rows [c*cl, (c+1)*cl) of its local
            (lps, ...) trunk leaves."""
            return jax.tree.map(
                lambda l: lax.dynamic_slice_in_dim(
                    l, c * chunk_l, chunk_l, axis=0
                ),
                tree,
            )

        def chunk_add(gtree, c, d):
            """Accumulate a chunk's gradient into its slice of the local
            (lps, ...) gradient leaves."""
            return jax.tree.map(
                lambda g, dd: lax.dynamic_update_slice_in_dim(
                    g,
                    lax.dynamic_slice_in_dim(g, c * chunk_l, chunk_l, axis=0)
                    + dd,
                    c * chunk_l,
                    axis=0,
                ),
                gtree,
                d,
            )

        def step_interleaved(params, opt_state, x, y, valid):
            s, v0, v, mb, t_len, is_last, denom = stage_context(x, valid)
            micro_tok = x.reshape(m_count, mb, t_len)
            labels = y.reshape(m_count, mb, t_len)

            def at(row):
                return lax.dynamic_index_in_dim(row, s, 0, keepdims=False)

            def tick(carry, xs):
                fm_row, fc_row, fa_row, bm_row, bc_row, ba_row = xs
                (
                    ring, pend_act, act_rx, pend_ct, ct_rx,
                    g_emb, g_trunk, g_head, ce_acc,
                ) = carry
                # sticky rx: refresh only when the neighbor really sent
                act_rx = jnp.where(at(fa_row), pend_act, act_rx)
                ct_rx = jnp.where(at(ba_row), pend_ct, ct_rx)

                # ---- forward work item ----
                fm, fc_ = at(fm_row), at(fc_row)
                do_f = fm >= 0
                fmc = jnp.clip(fm, 0, m_count - 1)
                tok_f = lax.dynamic_index_in_dim(
                    micro_tok, fmc, 0, keepdims=False
                )
                lbl_f = lax.dynamic_index_in_dim(
                    labels, fmc, 0, keepdims=False
                )
                emb_f = embed_apply({"params": params["embed"]}, tok_f)
                entry = (s == 0) & (fc_ == 0)  # a fresh micro enters here
                inp = jnp.where(entry, emb_f, act_rx)
                slot_f = jnp.mod(fmc, rk)
                prev = lax.dynamic_slice(
                    ring, (fc_, slot_f, 0, 0, 0), (1, 1) + ring.shape[2:]
                )[0, 0]
                ring = lax.dynamic_update_slice(
                    ring,
                    jnp.where(do_f, inp, prev)[None, None],
                    (fc_, slot_f, 0, 0, 0),
                )
                out_f, ce_f = stage_all(
                    chunk_slice(params["trunk"], fc_), params["head"],
                    inp, lbl_f,
                )
                pend_act = lax.ppermute(out_f, pipe_axis, fwd)
                head_site = is_last & (fc_ == v_chunks - 1)
                ce_acc = ce_acc + ce_f * (head_site & do_f).astype(
                    jnp.float32
                )

                # ---- backward work item ----
                bm, bc_ = at(bm_row), at(bc_row)
                do_b = bm >= 0
                do_bf = do_b.astype(jnp.float32)
                bmc = jnp.clip(bm, 0, m_count - 1)
                inp_b = lax.dynamic_slice(
                    ring,
                    (bc_, jnp.mod(bmc, rk), 0, 0, 0),
                    (1, 1) + ring.shape[2:],
                )[0, 0]
                tok_b = lax.dynamic_index_in_dim(
                    micro_tok, bmc, 0, keepdims=False
                )
                lbl_b = lax.dynamic_index_in_dim(
                    labels, bmc, 0, keepdims=False
                )
                (out_b, _), vjp_fn = jax.vjp(
                    lambda tr, hp, i: stage_all(tr, hp, i, lbl_b),
                    chunk_slice(params["trunk"], bc_),
                    params["head"],
                    inp_b,
                )
                head_site_b = is_last & (bc_ == v_chunks - 1)
                ct_out = (
                    jnp.where(head_site_b, jnp.zeros_like(out_b), ct_rx)
                    * do_bf.astype(out_b.dtype)
                )
                ct_ce = head_site_b.astype(jnp.float32) * do_bf
                d_chunk, d_head, d_inp = vjp_fn((ct_out, ct_ce))
                g_trunk = chunk_add(g_trunk, bc_, d_chunk)
                g_head = jax.tree.map(jnp.add, g_head, d_head)
                # the cotangent leaves the pipeline where the micro entered
                exit_site = (s == 0) & (bc_ == 0)
                d_emb_ct = jnp.where(
                    exit_site, d_inp, jnp.zeros_like(d_inp)
                )
                _, evjp = jax.vjp(
                    lambda ep: embed_apply({"params": ep}, tok_b),
                    params["embed"],
                )
                (d_embp,) = evjp(d_emb_ct)
                g_emb = jax.tree.map(jnp.add, g_emb, d_embp)
                pend_ct = lax.ppermute(d_inp, pipe_axis, rev)
                return (
                    ring, pend_act, act_rx, pend_ct, ct_rx,
                    g_emb, g_trunk, g_head, ce_acc,
                ), None

            act_dtype = jnp.dtype(compute_dtype)
            vary = lambda z: lax.pcast(z, axis_names, to="varying")  # noqa: E731
            zeros_act = vary(jnp.zeros((mb, t_len, d_model), act_dtype))
            g0 = jax.tree.map(
                lambda p: vary(jnp.zeros_like(p)), params
            )
            carry0 = (
                vary(
                    jnp.zeros(
                        (v_chunks, rk, mb, t_len, d_model), act_dtype
                    )
                ),
                zeros_act, zeros_act, zeros_act, zeros_act,
                g0["embed"], g0["trunk"], g0["head"],
                vary(jnp.float32(0.0)),
            )
            (*_, g_emb, g_trunk, g_head, ce_total), _ = lax.scan(
                tick, carry0, tick_xs
            )
            return hand_epilogue(
                params, opt_state, g_emb, g_trunk, g_head, ce_total,
                v, v0, denom,
            )

        batch_spec = P(self.data_axis)
        self._data_sharding = NamedSharding(mesh, batch_spec)
        self._valid_sharding = NamedSharding(mesh, P(self.data_axis))
        # each stage runs FULL-sequence local attention, so the flash
        # kernel can dispatch at kernel-friendly shapes
        self._check_vma = step_check_vma(
            seq_len=seq_len, head_dim=d_model // n_heads,
            compress=compress, overlap=overlap,
            hand_scheduled=schedule in ("1f1b", "interleaved"),
        )
        step_fns = {
            "gpipe": step,
            "1f1b": step_1f1b,
            "interleaved": step_interleaved,
        }
        mapped = jax.shard_map(
            step_fns[schedule],
            mesh=mesh,
            in_specs=(
                self._param_specs,
                self._opt_specs,
                batch_spec,
                batch_spec,
                P(self.data_axis),
            ),
            out_specs=(self._param_specs, self._opt_specs, P(), P()),
            check_vma=self._check_vma,
        )
        self._step = jax.jit(mapped, donate_argnums=(0, 1))
        # reused by train_chain's on-device loop (any schedule)
        self._raw_step = step_fns[schedule]
        self._replicated = NamedSharding(mesh, P())
        self._chains: dict = {}

    # -- stepping ------------------------------------------------------------

    def train_step(
        self,
        tokens: np.ndarray,
        labels: np.ndarray,
        valid: Sequence[float] | None = None,
    ) -> PipelineStepMetrics:
        """One step on a GLOBAL (batch, seq_len) token array; batch divisible
        by dp * microbatches."""
        per_step = self.dp * self.microbatches
        if (
            self._data_sharding.is_fully_addressable
            and tokens.shape[0] % per_step
        ):
            # pod runtime: callers pass HOST-LOCAL rows (place_tokens' seam)
            raise ValueError(
                f"global batch {tokens.shape[0]} not divisible by "
                f"dp*microbatches={per_step}"
            )
        if tokens.shape[1] != self.seq_len:
            raise ValueError(
                f"sequence length {tokens.shape[1]} != {self.seq_len}"
            )
        from akka_allreduce_tpu.train.trainer import (
            normalize_valid,
            place_mask,
            place_tokens,
        )

        valid_arr = normalize_valid(valid, self.dp)
        xd, yd = place_tokens(
            tokens, labels, self._data_sharding,
            seq_len=self.seq_len, dp=1,  # dp*microbatches checked above
        )
        vd = place_mask(valid_arr, self._valid_sharding)
        self.params, self.opt_state, loss, cnt = self._step(
            self.params, self.opt_state, xd, yd, vd
        )
        self.step_num += 1
        return PipelineStepMetrics(
            step=self.step_num, loss=float(loss), contributors=float(cnt)
        )

    def train(self, batches) -> list[PipelineStepMetrics]:
        return [self.train_step(x, y) for x, y in batches]

    # -- on-device training chain (no host I/O per step) ---------------------

    def _build_chain(self, sampler, steps: int, rows_per_replica: int):
        raw_step = self._raw_step
        data_axis = self.data_axis

        def chain(params, opt_state, key, valid):
            # one stream per DP replica row; all pipe stages of a row fold
            # the same data coordinate, so they agree on the row's tokens
            # (stage 0 injects, the last stage reads labels)
            rkey = jax.random.fold_in(key, lax.axis_index(data_axis))

            def body(carry, i):
                p, o = carry
                k = jax.random.fold_in(rkey, i)
                x, y = sampler(k, rows_per_replica)
                p, o, loss, cnt = raw_step(p, o, x, y, valid)
                return (p, o), (loss, cnt)

            (params, opt_state), (losses, cnts) = lax.scan(
                body, (params, opt_state), jnp.arange(steps)
            )
            return params, opt_state, losses, cnts

        mapped = jax.shard_map(
            chain,
            mesh=self.mesh,
            in_specs=(
                self._param_specs,
                self._opt_specs,
                P(),
                P(self.data_axis),
            ),
            out_specs=(self._param_specs, self._opt_specs, P(), P()),
            # same vma caveats as the step's shard_map (overlap / flash)
            check_vma=self._check_vma,
        )
        return jax.jit(mapped, donate_argnums=(0, 1))

    def train_chain(
        self,
        sampler,
        steps: int,
        rows_per_replica: int,
        *,
        valid: Sequence[float] | None = None,
        seed: int = 0,
    ) -> list[PipelineStepMetrics]:
        """Run ``steps`` DP x PP steps entirely on device in ONE dispatch
        (``rows_per_replica`` must divide by ``microbatches``)."""
        if rows_per_replica % self.microbatches:
            raise ValueError(
                f"rows_per_replica {rows_per_replica} not divisible by "
                f"{self.microbatches} microbatches"
            )
        from akka_allreduce_tpu.train.trainer import run_chain_cached

        losses, cnts = run_chain_cached(
            self,
            sampler,
            steps,
            rows_per_replica,
            lambda: self._build_chain(sampler, steps, rows_per_replica),
            valid,
            self.dp,
            self._valid_sharding,
            seed,
        )
        out = []
        for loss, cnt in zip(losses, cnts):
            self.step_num += 1
            out.append(
                PipelineStepMetrics(
                    step=self.step_num, loss=float(loss), contributors=float(cnt)
                )
            )
        return out

    # -- checkpoint seam: logical layer order, schedule-portable ------------

    @staticmethod
    def _is_params_container(t) -> bool:
        """A dict mirroring the params layout (optax moments do)."""
        return isinstance(t, dict) and "trunk" in t

    def _map_trunk_order(self, tree, order):
        """Reindex every trunk leaf's layer dim by ``order`` (host-side
        numpy take), for params AND optax moment containers. Identity
        permutation (gpipe/1f1b) is a no-op."""
        if np.array_equal(order, np.arange(len(order))):
            return tree

        def reorder(container):
            out = dict(container)
            out["trunk"] = jax.tree.map(
                lambda l: np.asarray(l)[order], container["trunk"]
            )
            return out

        return jax.tree.map(
            lambda t: reorder(t) if self._is_params_container(t) else t,
            tree,
            is_leaf=self._is_params_container,
        )

    def checkpoint_capture(self) -> dict:
        """Shard-local device state for the async checkpoint path: trunk
        leaves stage-sharded, still on device. The async checkpointer
        copies these HBM-to-HBM and drains them to host in the background
        (VERDICT r4 #1); :meth:`checkpoint_assemble` un-permutes on the
        writer thread."""
        return {"params": self.params, "opt_state": self.opt_state}

    def checkpoint_assemble(self, host: dict) -> dict:
        """Pure-host (numpy) re-order of a captured tree into LOGICAL
        layer order. Runs on the checkpoint writer thread — must not touch
        a device."""
        return self._map_trunk_order(
            {"params": host["params"], "opt_state": host["opt_state"]},
            self._layer_perm_inv,
        )

    def checkpoint_state(self) -> dict:
        """Serialize with trunk leaves in LOGICAL layer order, so a
        checkpoint written under any schedule (gpipe / 1f1b / interleaved,
        any virtual_chunks) restores under any other — the device-storage
        permutation never leaks into the format. Synchronous — the async
        checkpointer uses capture/assemble directly."""
        host = jax.tree.map(lambda x: np.asarray(x), self.checkpoint_capture())
        return self.checkpoint_assemble(host)

    def checkpoint_template(self) -> dict:
        """ShapeDtypeStruct twin (reordering preserves shapes/dtypes)."""
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(jnp.shape(l), jnp.asarray(l).dtype),
            {"params": self.params, "opt_state": self.opt_state},
        )

    def restore_checkpoint_state(self, state: dict) -> None:
        stored = self._map_trunk_order(
            {"params": state["params"], "opt_state": state["opt_state"]},
            self._layer_perm,
        )
        is_spec = lambda x: isinstance(x, P)  # noqa: E731
        place = lambda t, specs: jax.device_put(  # noqa: E731
            t,
            jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), specs, is_leaf=is_spec
            ),
        )
        self.params = place(stored["params"], self._param_specs)
        self.opt_state = place(stored["opt_state"], self._opt_specs)

    def logical_params(self) -> dict:
        """Params with trunk leaves in LOGICAL layer order (host arrays).

        The interleaved schedule stores the trunk in device-traversal
        order (stage-major chunks — see the stacking comment in __init__);
        external views un-permute so cross-schedule comparisons and
        checkpoints see the same model regardless of schedule."""
        host = jax.tree.map(lambda l: np.asarray(l), self.params)
        return self._map_trunk_order(host, self._layer_perm_inv)

    def get_flat_params(self) -> np.ndarray:
        from akka_allreduce_tpu.binder.api import flatten_pytree

        return flatten_pytree(self.logical_params())[0]

    def set_flat_params(self, vec: np.ndarray) -> None:
        """Inverse of :meth:`get_flat_params` (the binder's deposit seam):
        a flat LOGICAL-order vector unflattens into the params tree, the
        trunk re-permutes into this schedule's device-storage order, and
        the leaves re-place onto the current mesh. Optimizer state is
        untouched — the elastic-averaging pull adjusts weights only,
        exactly like ``DPTrainer.set_flat_params``."""
        from jax.flatten_util import ravel_pytree

        host = self.logical_params()
        flat, unravel = ravel_pytree(host)
        if vec.shape != flat.shape:
            raise ValueError(
                f"expected flat params of shape {flat.shape}, got {vec.shape}"
            )
        logical = unravel(jnp.asarray(vec, jnp.float32))
        stored = self._map_trunk_order(
            jax.tree.map(np.asarray, logical), self._layer_perm
        )
        is_spec = lambda x: isinstance(x, P)  # noqa: E731
        self.params = jax.device_put(
            stored,
            jax.tree.map(
                lambda s: NamedSharding(self.mesh, s),
                self._param_specs,
                is_leaf=is_spec,
            ),
        )
