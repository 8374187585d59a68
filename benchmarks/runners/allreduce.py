"""Runner for cells that time ``build_threshold_allreduce`` rounds, one after
another, each ended by ``block_until_ready`` on ``(sum, count)``.

The payload is the benchmark's (``reference/masked_sum.py``): made on the
devices from ``(seed, round)`` in one elementwise pass, resident and ready
before a round's clock starts (the input is donated, so every round gets a
new one). After each round a few seeded elements of ``sum`` and ``count`` are
kept; once the window has closed the host makes those elements again and
compares every round, and the last round is compared in full on the device
against the plain masked sum computed without a collective. The comparison is
exact: the payload's sums have one right bit pattern.
"""

from __future__ import annotations

import time

import numpy as np

from harness import spec, traffic
from harness.flops import allreduce_bus_bytes


class Runner:
    spans = ("make_input", "allreduce_call", "probe")

    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.cfg = ctx.cell.config
        self.traffic = ctx.cell.traffic
        self.ref = spec.load_module("reference", self.cfg["reference"])
        self.n = len(ctx.devices)
        self.floats = int(self.cfg["floats_per_device"])
        self.kept: list = []  # (round, mask, probes on device)
        self.ahead: dict = {}  # round -> its payload, made one round early

    def _mask(self, round_: int) -> np.ndarray:
        return traffic.contributor_mask(self.traffic, self.n, self.ctx.seed, round_)

    def setup(self) -> dict:
        t = time.perf_counter()
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from akka_allreduce_tpu.comm.allreduce import build_threshold_allreduce
        from akka_allreduce_tpu.parallel import line_mesh

        cfg, seed, floats = self.cfg, self.ctx.seed, self.floats
        phases = {"import_program_s": time.perf_counter() - t}
        if cfg["dtype"] != "float32" or cfg["mesh"] != "line":
            raise ValueError("this runner times float32 payloads on a line mesh")
        t = time.perf_counter()
        mesh = line_mesh(devices=self.ctx.devices)
        axis = mesh.axis_names[0]
        self.sharded = NamedSharding(mesh, P(axis))
        self.fn = build_threshold_allreduce(
            mesh, schedule=cfg["schedule"], compress=cfg["compress"],
            bucket_size=cfg["bucket_size"], donate=cfg["donate"],
        )
        ref = self.ref

        def make(round_):
            device = lax.axis_index(axis).astype(jnp.uint32)
            offset = ref.stream_offset_u32(seed, round_, device, jnp)
            index = lax.iota(jnp.uint32, floats)
            return ref.payload(index, offset, jnp)[None, :]

        self.make_input = jax.jit(jax.shard_map(
            make, mesh=mesh, in_specs=P(), out_specs=P(axis)
        ))
        self.index = traffic.probe_indices(self.traffic, floats, seed)
        index = jnp.asarray(self.index)
        self.probe = jax.jit(lambda total, count: jnp.stack(
            [total[index], count[index].astype(total.dtype)]
        ))
        phases["build_s"] = time.perf_counter() - t
        self.warm = int(self.traffic["warmup_units"])
        round_s = []
        for r in range(self.warm):  # the window's own calls, untimed
            self.prepare(r - self.warm)
            t = time.perf_counter()
            self.unit(r - self.warm)
            round_s.append(time.perf_counter() - t)
            self.after_unit(r - self.warm)
        phases["warmup_round_s"] = round_s
        return phases

    # -- the window: unit i is round ``warm + i`` --------------------------------

    def prepare(self, i: int) -> None:
        import jax

        self.round = self.warm + i
        self.mask = self._mask(self.round)
        with self.ctx.span("make_input"):
            # this round's payload was made a round ahead (two are resident,
            # as a sync that pipelines its buckets holds them); make the next
            self.xs = self.ahead.pop(self.round, None)
            if self.xs is None:
                self.xs = self.make_input(np.uint32(self.round))
            self.ahead = {self.round + 1: self.make_input(np.uint32(self.round + 1))}
            self.valid = jax.device_put(self.mask, self.sharded)
            jax.block_until_ready((self.xs, self.ahead, self.valid))

    def unit(self, i: int) -> dict:
        import jax

        with self.ctx.span("allreduce_call"):
            self.out = self.fn(self.xs, self.valid)
            jax.block_until_ready(self.out)
        return {"work": allreduce_bus_bytes(self.floats, 4, self.n), "ok": True}

    def after_unit(self, i: int) -> None:
        with self.ctx.span("probe"):
            self.kept.append((self.round, self.mask, self.probe(*self.out)))

    def close_window(self) -> dict:
        """Every round's kept answers against the host's: exact."""
        idx = self.index
        bad_rounds, bad_probes = 0, 0
        for round_, mask, probes in self.kept:
            got = np.asarray(probes)
            want_sum, want_count = self.ref.masked_sum(
                idx, self.ctx.seed, round_, mask, np
            )
            wrong = int(np.sum(got[0] != want_sum) + np.sum(got[1] != want_count))
            bad_probes += wrong
            bad_rounds += bool(wrong) and round_ >= self.warm
        self.bad_probes = bad_probes
        return {"work_unit": "bus_bytes", "failed_late": bad_rounds,
                "rounds_compared": len(self.kept), "probes_a_round": len(idx)}

    # -- the check ---------------------------------------------------------------

    def check(self) -> list[dict]:
        """The last round in full, on the first device, without a collective."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        first = self.ctx.devices[0]
        total, count = (
            next(s.data for s in a.addressable_shards if s.device == first)
            for a in self.out
        )
        seed, round_, mask, ref = self.ctx.seed, self.round, self.mask, self.ref

        @jax.jit
        def errors(total, count):
            want, n = ref.masked_sum(
                lax.iota(jnp.uint32, total.shape[0]), seed, round_, mask, jnp
            )
            return (jnp.max(jnp.abs(total - want)), jnp.max(jnp.abs(count - n)),
                    jnp.max(jnp.abs(want)))

        sum_err, count_err, scale = (float(v) for v in errors(total, count))
        limits = self.cfg["correct_limits"]
        values = {
            "sum_max_abs_err": sum_err, "count_max_abs_err": count_err,
            "probe_mismatches": float(self.bad_probes),
        }
        out = [
            {"name": n, "value": v, "limit": limits[n], "ok": v <= limits[n]}
            for n, v in values.items()
        ]
        out.append({"name": "last_round", "round": round_, "mask": mask.tolist(),
                    "max_abs_expected": scale, "elements": int(total.shape[0]),
                    "ok": scale > 0.5})
        return out
