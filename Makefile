# Top-level developer targets. The native build's canonical recipe lives in
# akka_allreduce_tpu/native/__init__.py (see native/Makefile, a thin shim).

PYTHON ?= python3

.PHONY: lint lint-json lint-sarif baseline native test tier1 trace-demo bench-wire chaos chaos-recover chaos-failover chaos-adapt chaos-gossip chaos-scale chaos-train

# arlint scan surface: the package, the entry shims at the repo root, and the
# tests' subprocess worker helpers (async/thread code runs there too). Narrow
# it per-path with the [tool.arlint] exclude list, never by trimming this.
LINT_PATHS = akka_allreduce_tpu/ chip_smoke.py $(wildcard tests/*_worker.py)

# arlint: async-safety / buffer-aliasing / wire-contract / thread-race /
# determinism analyzer (ANALYSIS.md). Exit 1 on any unsuppressed finding —
# same gate as tests/test_arlint.py, so CI and a local `make lint` agree.
lint:
	$(PYTHON) -m akka_allreduce_tpu.analysis $(LINT_PATHS)

lint-json:
	$(PYTHON) -m akka_allreduce_tpu.analysis $(LINT_PATHS) --json

# SARIF 2.1.0 log for code-scanning upload in any CI (plus the normal text
# report); exit code contract identical to `make lint`
lint-sarif:
	$(PYTHON) -m akka_allreduce_tpu.analysis $(LINT_PATHS) --sarif arlint.sarif

# refresh arlint_baseline.json from the current tree — use ONLY for findings
# that are deliberate and justified; prefer fixing, then inline suppression
baseline:
	$(PYTHON) -m akka_allreduce_tpu.analysis $(LINT_PATHS) --write-baseline

native:
	$(MAKE) -C native

# observability demo (OBSERVABILITY.md): run a tiny 2-process local cluster,
# emit per-process Perfetto traces + metrics snapshots, and merge them into
# trace_demo/trace.json (open at https://ui.perfetto.dev). The same flow is
# asserted well-formed by tests/test_obs_cluster.py in tier-1.
trace-demo:
	JAX_PLATFORMS=cpu $(PYTHON) -m akka_allreduce_tpu obs demo --out-dir trace_demo

# deterministic host data-plane microbench (BENCHMARKS.md rounds 8-9):
# wire codec throughput (encode+checksum / decode+verify), the syscall-
# batching levers (one sendmsg per frame vs one sendmmsg per burst, plus
# the recvmmsg mirror) over loopback — interleaved legs, JSON medians —
# and one record per data plane v3 lever: io_uring vs sendmmsg (or the
# probe's fallback reason on a kernel without io_uring), the one-chunk-
# round intra-chunk striping A/B over per-stream-paced drains, and the
# congestion scheduler's deterministic shed/restore trajectory.
bench-wire:
	JAX_PLATFORMS=cpu $(PYTHON) -m akka_allreduce_tpu bench-wire --json \
	  --uring --intra-chunk --congestion

# fixed-seed 30-second chaos soak (RESILIENCE.md): real master + 3 node
# processes under seeded drop/delay/corruption + a mid-run partition that
# heals; exits non-zero unless rounds completed UNDER the chaos. The same
# seed replays the same per-process chaos event logs (chaos_run/*.jsonl).
chaos:
	JAX_PLATFORMS=cpu $(PYTHON) -m akka_allreduce_tpu chaos --seed 1234 \
	  --duration 30 --nodes 3 --th 0.66 --streams 2 --gossip \
	  --uring --intra-chunk 1048576 --congestion \
	  --out-dir chaos_run \
	  --spec "drop:p=0.05;delay:ms=10;corrupt:p=0.02;partition:groups=m+0+1|2,at=10s,heal=8s"

# fixed-seed crash + disk-loss recovery drill (RESILIENCE.md "Recovery"):
# one node's seeded chaos crash is followed by deleting its checkpoint
# directory; the respawned node must restore its state from live peer
# replicas (byte-identical blobs) and the round budget must still finish.
# Exit 0 iff every assertion holds; tests/test_peer_restore.py runs the
# same scenario inside tier-1.
chaos-recover:
	JAX_PLATFORMS=cpu timeout -k 15 420 $(PYTHON) -m akka_allreduce_tpu \
	  chaos-recover --seed 1234 --streams 2 --gossip \
	  --uring --intra-chunk 1048576 --congestion \
	  --out-dir chaos_recover_run

# fixed-seed master-kill failover drill (RESILIENCE.md "Tier 4"): a seeded
# chaos crash kills the LEADER mid-round; the warm standby must take over
# under a bumped epoch, the round budget must complete with no round applied
# twice (cross-epoch dedup), and a node killed + disk-wiped AFTER the
# failover must still peer-restore via the replicated holder registry.
chaos-failover:
	JAX_PLATFORMS=cpu timeout -k 15 420 $(PYTHON) -m akka_allreduce_tpu \
	  chaos-failover --seed 1234 --streams 2 --gossip \
	  --uring --intra-chunk 1048576 --congestion \
	  --out-dir chaos_failover_run

# fixed-seed adaptive-degradation drill (RESILIENCE.md "Tier 5"): a seeded
# staged straggler (windowed targeted delay + a stall burst) slows one
# node; the leader's AdaptiveController must degrade (lower th_reduce,
# f16 -> int8 wire) within K rounds, hold without oscillation, restore to
# full fidelity after the heal, and every node's reduced values (identical
# payloads, --uniform-check) must stay within the EF error budget.
chaos-adapt:
	JAX_PLATFORMS=cpu timeout -k 15 420 $(PYTHON) -m akka_allreduce_tpu \
	  chaos-adapt --seed 1234 --streams 2 --gossip \
	  --uring --intra-chunk 1048576 --congestion --out-dir chaos_adapt_run

# fixed-seed decentralized-membership drill (RESILIENCE.md "Tier 6"): a
# seeded ONE-DIRECTIONAL partition cuts one node's sends to the master
# while SWIM gossip membership is armed — the indirect-probe path must
# keep the healthy node in the cluster (zero expulsions, rounds keep
# completing), and a node killed for real afterwards must still be
# confirmed dead by the ring and expelled.
chaos-gossip:
	JAX_PLATFORMS=cpu timeout -k 15 420 $(PYTHON) -m akka_allreduce_tpu \
	  chaos-gossip --seed 1234 --streams 2 \
	  --uring --intra-chunk 1048576 --congestion --out-dir chaos_gossip_run

# fixed-seed pod-scale control-plane drill (RESILIENCE.md "Scale"): the
# largest real-process grid this box runs — a 2x8 pod (16 nodes, ids
# anchored to grid coordinates via --grid/--process-index) sharded into
# 4 free-running LineMasters, plus a leader and a warm standby — through
# a one-way partition (zero re-shards), a leader SIGKILL (epoch-2
# takeover rebuilding the SAME shard layout, every shard resuming its
# own sequence), and a node SIGKILL (only its coordinate-anchored shard
# shrinks). The summary JSON also records the deterministic Fabric's
# sim rate (the 256..1024-node sims' cost evidence). Exit 0/1.
chaos-scale:
	JAX_PLATFORMS=cpu timeout -k 15 480 $(PYTHON) -m akka_allreduce_tpu \
	  chaos-scale --seed 1234 --grid 2x8 --line-shards 4 --streams 2 \
	  --uring --intra-chunk 1048576 --congestion --out-dir chaos_scale_run

# fixed-seed workload-resilience drill (RESILIENCE.md "Tier 7"): a real
# 4-node cluster where every node drives an ElasticTrainer-wrapped REAL
# pipeline-parallel trainer; a seeded chaos crash kills one node
# mid-train-step, every survivor must RESTAGE the layer stack over the
# surviving pipe axis (snapshot -> rebuild -> restore, no optimizer state
# lost — the loss curve resumes inside the pinned band), rounds must keep
# completing at the reduced membership, and the run must end gracefully.
# tests/test_chaos_train.py runs the same drill's fastest (dp) arm in
# tier-1.
chaos-train:
	JAX_PLATFORMS=cpu timeout -k 15 560 $(PYTHON) -m akka_allreduce_tpu \
	  chaos-train --seed 1234 --family pipeline --streams 2 --gossip \
	  --uring --intra-chunk 1048576 --congestion \
	  --out-dir chaos_train_run

# the driver's form: six loadfile workers (~7 min on 8 cores; serially the
# files sum to ~40 min). tests/conftest.py gives every test a 300 s limit.
test:
	JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 $(PYTHON) -m pytest tests/ \
	  -q -m 'not slow' -p xdist -n 6 --dist loadfile -p no:cacheprovider

tier1: lint test
