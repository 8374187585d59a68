"""arlint self-test + tier-1 enforcement.

Two jobs, per ISSUE 3:

1. **Rule self-test** — every rule has at least one positive fixture (the
   motivating bug shape, reduced) and one negative fixture (the correct
   idiom the codebase actually uses), so a rule regression is caught by the
   fixture and not by a silently-green package scan.
2. **Enforcement** — the analyzer runs over the installed package and must
   report ZERO unsuppressed findings. Re-seeding any motivating bug (the
   dropped create_task handle test below does exactly that on a copy of
   ``control/remote.py``) makes this suite fail.

Tier-1: no ``slow`` marker, stdlib-only, sub-second.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import akka_allreduce_tpu
from akka_allreduce_tpu.analysis import (
    ArlintConfig,
    analyze_paths,
    analyze_source,
    load_config,
)
from akka_allreduce_tpu.analysis.config import (
    ConfigError,
    config_from_table,
    _read_arlint_table_minitoml,
)
from akka_allreduce_tpu.analysis.core import (
    apply_baseline,
    load_baseline,
    write_baseline,
)

PKG_DIR = Path(akka_allreduce_tpu.__file__).parent
REPO_ROOT = PKG_DIR.parent


def rules_of(source: str, **cfg) -> list[str]:
    return [
        f.rule
        for f in analyze_source(textwrap.dedent(source), config=ArlintConfig(**cfg))
    ]


# -- ASYNC001: blocking call in coroutine -------------------------------------


def test_async001_positive_blocking_sleep_and_subprocess():
    src = """
    import time, subprocess
    async def tick():
        time.sleep(1.0)
        subprocess.run(["true"])
    """
    assert rules_of(src) == ["ASYNC001", "ASYNC001"]


def test_async001_negative_async_sleep_and_sync_context():
    src = """
    import asyncio, time
    async def tick():
        await asyncio.sleep(1.0)
    def sync_tick():
        time.sleep(1.0)  # blocking is fine off the event loop
    async def outer():
        def helper():
            time.sleep(0.1)  # runs in whatever thread CALLS it, not here
        return helper
    """
    assert rules_of(src) == []


def test_async001_configurable_denylist():
    src = """
    async def f():
        util.block_hard()
    """
    assert rules_of(src) == []
    assert rules_of(src, async001_blocking=("util.block_hard",)) == ["ASYNC001"]


# -- ASYNC002: un-awaited coroutine ------------------------------------------


def test_async002_positive_unawaited_local_and_asyncio():
    src = """
    import asyncio
    async def work(): ...
    async def main(self):
        work()
        asyncio.sleep(1)
    class T:
        async def _beat(self): ...
        async def run(self):
            self._beat()
    """
    assert rules_of(src) == ["ASYNC002", "ASYNC002", "ASYNC002"]


def test_async002_negative_awaited_or_retained():
    src = """
    import asyncio
    async def work(): ...
    async def main():
        await work()
        t = asyncio.get_running_loop().create_task(work())
        await t
    def sync_fn(work_fn):
        work_fn()  # unknown callable: not assumed to be a coroutine
    """
    assert rules_of(src) == []


# -- ASYNC003: dropped task handle --------------------------------------------


def test_async003_positive_dropped_handles():
    src = """
    import asyncio
    async def main(loop, coro):
        asyncio.create_task(coro)
        loop.create_task(coro)
        asyncio.ensure_future(coro)
    """
    assert rules_of(src) == ["ASYNC003"] * 3


def test_async003_negative_retained_or_observed():
    src = """
    import asyncio
    async def main(self, coro, tasks):
        self._pump = asyncio.create_task(coro)
        tasks.add(asyncio.create_task(coro))
        t = asyncio.ensure_future(coro)
        await t
    """
    assert rules_of(src) == []


# -- ASYNC004: cancellation-swallowing except ---------------------------------


def test_async004_positive_broad_excepts():
    src = """
    async def pump():
        try:
            step()
        except Exception:
            pass
    async def pump2():
        try:
            step()
        except:
            pass
    async def pump3():
        try:
            step()
        except (ValueError, BaseException):
            log()
    """
    assert rules_of(src) == ["ASYNC004"] * 3


def test_async004_negative_escaped_or_sync():
    src = """
    import asyncio
    async def pump():
        try:
            step()
        except asyncio.CancelledError:
            raise
        except Exception:
            log()
    async def connect(sock):
        try:
            step()
        except BaseException:
            sock.close()
            raise
    async def stop(task):
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass  # the idiomatic cancel-and-reap
    def sync_handler():
        try:
            step()
        except Exception:
            pass  # no event loop here
    """
    assert rules_of(src) == []


# -- BUF001: escaping view of recycled buffer ---------------------------------


def test_buf001_positive_escaping_views():
    src = """
    import numpy as np
    class Receiver:
        def stash(self):
            self._view = np.frombuffer(self._ring, dtype="<f4")
        def hand_out(self):
            return memoryview(self._recv_pool[0])[4:]
        def gen(self):
            yield np.frombuffer(self.ring, dtype="<f4")
    """
    assert rules_of(src) == ["BUF001"] * 3


def test_buf001_negative_copies_and_unmarked_sources():
    src = """
    import numpy as np
    class Receiver:
        def local_use(self):
            view = np.frombuffer(self._ring, dtype="<f4")
            return view.copy()
        def unmarked(self, value):
            return np.frombuffer(value, dtype=np.float32)
        def copy_out(self, body, got, pos):
            body[:got] = memoryview(self._ring)[pos:pos + got]
    """
    assert rules_of(src) == []


def test_buf001_markers_configurable():
    src = """
    import numpy as np
    def f(self):
        return np.frombuffer(self._scratch, dtype="<f4")
    """
    assert rules_of(src) == []
    assert rules_of(src, buf001_markers=("scratch",)) == ["BUF001"]


# -- WIRE001: wire-tag exhaustiveness -----------------------------------------

_WIRE_MODULE = '''
_TAGS = {Ping: 1, Pong: 2}

def _encode_parts(msg):
    tag = _TAGS[type(msg)]
    if tag == 1:
        return [b"\\x01"]
    if tag == 2:
        return [b"\\x02"]

def decode(buf):
    tag = buf[0]
    if tag == 1:
        return Ping()
    PONG_ARM
'''

_DISPATCH_MODULE = """
def handle(msg):
    if isinstance(msg, Ping):
        return []
    PONG_DISPATCH
"""


def _wire_findings(tmp_path, pong_arm, pong_dispatch):
    (tmp_path / "wire.py").write_text(
        _WIRE_MODULE.replace("PONG_ARM", pong_arm)
    )
    (tmp_path / "worker.py").write_text(
        _DISPATCH_MODULE.replace("PONG_DISPATCH", pong_dispatch)
    )
    return analyze_paths(
        [tmp_path], ArlintConfig(rules=("WIRE001",)), root=tmp_path
    )


def test_wire001_positive_missing_decode_arm(tmp_path):
    found = _wire_findings(
        tmp_path, "pass", "if isinstance(msg, Pong): return []"
    )
    assert [f.rule for f in found] == ["WIRE001"]
    assert "tag 2 (Pong)" in found[0].message and "decode" in found[0].message


def test_wire001_positive_missing_dispatch_arm(tmp_path):
    found = _wire_findings(
        tmp_path, "if tag == 2:\n        return Pong()", "pass"
    )
    assert [f.rule for f in found] == ["WIRE001"]
    assert "Pong" in found[0].message and "dispatch" in found[0].message


def test_wire001_positive_orphan_arm(tmp_path):
    found = _wire_findings(
        tmp_path,
        "if tag == 2:\n        return Pong()\n    if tag == 3:\n        return Pang()",
        "if isinstance(msg, Pong): return []",
    )
    assert [f.rule for f in found] == ["WIRE001"]
    assert "tag 3" in found[0].message


def test_wire001_negative_exhaustive(tmp_path):
    found = _wire_findings(
        tmp_path,
        "if tag == 2:\n        return Pong()",
        "if isinstance(msg, Pong): return []",
    )
    assert found == []


# -- suppressions / baseline / config -----------------------------------------


def test_inline_suppression_same_line_and_next_line():
    src = """
    import time
    async def f():
        time.sleep(1)  # arlint: disable=ASYNC001
        # arlint: disable-next=ASYNC001
        time.sleep(2)
        time.sleep(3)  # arlint: disable=BUF001 (wrong rule: still reported)
    """
    assert rules_of(src) == ["ASYNC001"]


def test_blanket_suppression():
    src = """
    import time
    async def f():
        time.sleep(1)  # arlint: disable
    """
    assert rules_of(src) == []


def test_baseline_absorbs_exact_multiplicity(tmp_path):
    src = textwrap.dedent(
        """
        import time
        async def f():
            time.sleep(1)
        async def g():
            time.sleep(1)
        """
    )
    findings = analyze_source(src)
    assert [f.rule for f in findings] == ["ASYNC001", "ASYNC001"]
    bl = tmp_path / "baseline.json"
    write_baseline(bl, findings[:1])  # baseline covers ONE of the two
    fresh, known = apply_baseline(findings, load_baseline(bl))
    assert len(known) == 1 and len(fresh) == 1  # identical 2nd hit still fails


def test_baseline_missing_file_enforces_everything(tmp_path):
    assert load_baseline(tmp_path / "nope.json") == {}


def test_minitoml_reads_arlint_table():
    table = _read_arlint_table_minitoml(
        textwrap.dedent(
            """
            [tool.other]
            x = 1
            [tool.arlint]
            baseline = "arlint_baseline.json"
            exclude = [
                "fixtures",
                "generated",
            ]
            buf001-markers = ["ring", "pool"]
            """
        )
    )
    cfg = config_from_table(table)
    assert cfg.baseline == "arlint_baseline.json"
    assert cfg.exclude == ("fixtures", "generated")
    assert cfg.buf001_markers == ("ring", "pool")


def test_minitoml_rejects_unknown_key():
    try:
        config_from_table({"surprise": 1})
    except ConfigError:
        pass
    else:  # pragma: no cover
        raise AssertionError("unknown key must be a config error")


def test_async004_exception_arm_protected_by_later_dedicated_arm():
    """py3.8+: `except Exception` cannot catch CancelledError, so a dedicated
    arm AFTER it still guarantees escape — but bare/except BaseException
    catch it first, so a later dedicated arm is dead and must not protect."""
    after_exception = """
    import asyncio
    async def pump():
        try:
            step()
        except Exception:
            log()
        except asyncio.CancelledError:
            raise
    """
    assert rules_of(after_exception) == []
    after_bare = """
    import asyncio
    async def pump():
        try:
            step()
        except BaseException:
            log()
        except asyncio.CancelledError:
            raise
    """
    assert rules_of(after_bare) == ["ASYNC004"]


def test_suppression_inside_string_literal_is_not_a_suppression():
    src = '''
    import time
    async def f():
        log("how to silence: # arlint: disable"); time.sleep(1)
    '''
    assert rules_of(src) == ["ASYNC001"]


def test_wire001_single_file_skips_dispatch_check(tmp_path):
    """Linting just the wire module must not demand dispatch arms it cannot
    see (they live in worker/bootstrap); the arm-set checks still run."""
    (tmp_path / "wire.py").write_text(
        _WIRE_MODULE.replace("PONG_ARM", "if tag == 2:\n        return Pong()")
    )
    found = analyze_paths(
        [tmp_path / "wire.py"], ArlintConfig(rules=("WIRE001",)), root=tmp_path
    )
    assert found == []


def test_baseline_distinguishes_same_line_findings(tmp_path):
    """WIRE001 anchors every finding to the _TAGS literal: entries must be
    fingerprinted by message too, or one baselined finding would absorb any
    future different finding on that line."""
    found = _wire_findings(tmp_path, "pass", "pass")  # decode arm + dispatch
    assert len(found) == 2 and len({f.message for f in found}) == 2
    bl = tmp_path / "bl.json"
    write_baseline(bl, found[:1])
    fresh, known = apply_baseline(found, load_baseline(bl))
    assert len(known) == 1 and len(fresh) == 1


def test_minitoml_header_with_trailing_comment():
    table = _read_arlint_table_minitoml(
        "[tool.arlint]  # analyzer config\nbaseline = \"b.json\"\n"
    )
    assert table == {"baseline": "b.json"}


def test_minitoml_trailing_comments_on_values_and_lists():
    table = _read_arlint_table_minitoml(
        textwrap.dedent(
            """
            [tool.arlint]
            baseline = "b.json"  # content-fingerprinted
            exclude = [
                "fixtures",  # test snippets
            ]  # done
            [tool.other]
            x = 1
            """
        )
    )
    assert table == {"baseline": "b.json", "exclude": ["fixtures"]}
    # a '#' INSIDE a quoted value is data, not a comment
    table = _read_arlint_table_minitoml(
        '[tool.arlint]\nbaseline = "dir#1/b.json"\n'
    )
    assert table == {"baseline": "dir#1/b.json"}


def test_minitoml_unterminated_list_is_an_error():
    try:
        _read_arlint_table_minitoml('[tool.arlint]\nexclude = [\n "a",\n')
    except ConfigError:
        pass
    else:  # pragma: no cover
        raise AssertionError("unterminated list must not be silently dropped")


def test_async003_dropped_observed_task_is_flagged():
    """remote.observed_task keeps the task alive and logs crashes, but a
    dropped handle still loses cancel/await — same rule applies."""
    src = """
    async def main(coro):
        observed_task(coro, name="pump")
    """
    assert rules_of(src) == ["ASYNC003"]
    src_ok = """
    async def main(self, coro):
        self._pump = observed_task(coro, name="pump")
    """
    assert rules_of(src_ok) == []


def test_buf001_markers_match_segments_not_substrings():
    src = """
    def f(self):
        return memoryview(self._instring)
    def g(self):
        return memoryview(self.wiring_harness)
    """
    assert rules_of(src) == []


def test_observed_task_is_strongly_referenced_until_done():
    """The helper must close asyncio's weak-reference hole itself, not rely
    on callers retaining the handle."""
    import asyncio
    import gc

    from akka_allreduce_tpu.control import remote

    async def main():
        started = asyncio.Event()

        async def bg():
            started.set()
            await asyncio.sleep(0.05)
            return "done"

        remote.observed_task(bg(), name="drop-me")  # arlint: disable=ASYNC003
        assert any(
            t.get_name() == "drop-me" for t in remote._observed_tasks
        )
        gc.collect()  # without the strong ref this could reap the task
        await started.wait()
        await asyncio.sleep(0.1)
        assert not any(
            t.get_name() == "drop-me" for t in remote._observed_tasks
        )

    asyncio.run(main())


def test_async002_sync_context_and_cross_class_names_not_flagged():
    """A sync function may hand a coroutine to a scheduler, and `self.X()`
    in one class must not resolve against another class's async method."""
    src = """
    async def work(): ...
    def schedule(runner):
        work()  # handed to the runner below, not lost
    class Flusher:
        async def flush(self): ...
    class SyncSink:
        def flush(self): ...
        def run(self):
            self.flush()
    """
    assert rules_of(src) == []


def test_buf001_copy_in_same_expression_is_clean():
    """The rule's own advice — 'copy before the escape' — must silence it
    even when the copy wraps the view in one expression."""
    src = """
    import numpy as np
    class R:
        def a(self):
            return np.frombuffer(self._ring, dtype="<f4").copy()
        def b(self):
            self._hdr = bytes(memoryview(self._ring)[:4])
        def c(self):
            return np.frombuffer(self._ring, dtype="<f2").astype(np.float32)
    """
    assert rules_of(src) == []


def test_suppression_on_closing_line_of_wrapped_statement():
    src = """
    import time
    async def f(big_timeout):
        time.sleep(
            big_timeout,
        )  # arlint: disable=ASYNC001
    """
    assert rules_of(src) == []


def test_overlapping_paths_analyze_each_file_once(tmp_path):
    bad = tmp_path / "m.py"
    bad.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    found = analyze_paths([tmp_path, bad], ArlintConfig(), root=tmp_path)
    assert [f.rule for f in found] == ["ASYNC001"]


def test_lowercase_or_garbled_rule_list_never_becomes_blanket():
    """`disable=buf001` must suppress BUF001 (normalized), and a garbled
    list must suppress NOTHING — silently widening to a blanket disable
    would weaken the gate."""
    src = """
    import numpy as np
    import time
    class R:
        def f(self):
            return np.frombuffer(self._ring, dtype="<f4")  # arlint: disable=buf001
    async def g():
        time.sleep(1)  # arlint: disable=???
    """
    assert rules_of(src) == ["ASYNC001"]


def test_wire001_non_literal_tags_is_a_finding_not_a_silent_skip(tmp_path):
    (tmp_path / "wire.py").write_text(
        "_TAGS = {Ping: 1, Pong: NEXT_TAG}\n\ndef decode(buf):\n    tag = buf[0]\n"
    )
    found = analyze_paths(
        [tmp_path], ArlintConfig(rules=("WIRE001",)), root=tmp_path
    )
    assert [f.rule for f in found] == ["WIRE001"]
    assert "statically-readable" in found[0].message


def test_async002_same_name_sync_method_in_other_class_not_flagged():
    src = """
    class A:
        async def ping(self): ...
    class B:
        def ping(self): ...
        async def run(self):
            self.ping()  # B's SYNC ping: fine
    """
    assert rules_of(src) == []


def test_async004_raise_of_bound_name_counts_as_reraise():
    src = """
    async def pump():
        try:
            step()
        except Exception as e:
            log(e)
            raise e
    """
    assert rules_of(src) == []


def test_cli_unknown_rule_is_a_usage_error(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    r = _run_cli(str(bad), "--rules", "ASYNC01", "--no-baseline")
    assert r.returncode == 2
    assert "unknown rule" in r.stderr


# -- enforcement over the real package ----------------------------------------


def test_package_is_arlint_clean():
    """THE tier-1 gate: zero unsuppressed findings over the package, with
    the repo's own [tool.arlint] config + baseline applied."""
    config = load_config(pyproject=REPO_ROOT / "pyproject.toml")
    findings = analyze_paths([PKG_DIR], config, root=REPO_ROOT)
    bl_path = config.baseline_path()
    baseline = load_baseline(bl_path) if bl_path else {}
    fresh, _known = apply_baseline(findings, baseline)
    assert fresh == [], "unsuppressed arlint findings:\n" + "\n".join(
        f.render() for f in fresh
    )


def test_seeded_bug_in_real_transport_source_is_caught(tmp_path):
    """Acceptance check: re-seeding a motivating bug into a COPY of
    control/remote.py makes the analyzer fail — the enforcement test above
    would therefore fail on the real file too."""
    source = (PKG_DIR / "control" / "remote.py").read_text()
    assert analyze_source(source, "remote.py") == []  # clean as shipped
    seeded = source + textwrap.dedent(
        """
        async def _seeded_regression(transport, ep, sender):
            asyncio.create_task(transport._drain_sender(ep, sender))
        """
    )
    rules = [f.rule for f in analyze_source(seeded, "remote.py")]
    assert rules == ["ASYNC003"]


# -- CLI ----------------------------------------------------------------------


def _run_cli(*args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "akka_allreduce_tpu.analysis", *args],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
        timeout=120,
    )


def test_cli_reports_findings_and_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    r = _run_cli(str(bad), "--no-baseline")
    assert r.returncode == 1
    assert "ASYNC001" in r.stdout and "bad.py:3" in r.stdout
    bad.write_text("async def f(): ...\n")
    r = _run_cli(str(bad), "--no-baseline")
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_json_mode(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import asyncio\nasync def f(c):\n    asyncio.create_task(c)\n"
    )
    r = _run_cli(str(bad), "--json", "--no-baseline")
    assert r.returncode == 1
    report = json.loads(r.stdout)
    assert report["count"] == 1
    assert report["findings"][0]["rule"] == "ASYNC003"


def test_cli_write_baseline_roundtrip(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    bl = tmp_path / "bl.json"
    r = _run_cli(str(bad), "--baseline", str(bl), "--write-baseline")
    assert r.returncode == 0 and bl.is_file()
    r = _run_cli(str(bad), "--baseline", str(bl))
    assert r.returncode == 0, "baselined finding must not fail the run"


def test_cli_package_gate_matches_make_lint():
    """`make lint`'s exact invocation exits 0 on the shipped tree."""
    r = _run_cli("akka_allreduce_tpu/")
    assert r.returncode == 0, r.stdout + r.stderr


# -- v2: THRD001/THRD002 (execution-context races) -----------------------------


def _paths_findings(tmp_path, sources: dict[str, str], **cfg) -> list:
    """Write fixture files and run the full project-level pipeline."""
    for rel, src in sources.items():
        f = tmp_path / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(textwrap.dedent(src))
    return analyze_paths([tmp_path], ArlintConfig(**cfg), root=tmp_path)


def test_thrd001_positive_unlocked_cross_context_mutation(tmp_path):
    findings = _paths_findings(
        tmp_path,
        {
            "pump.py": """
            import threading

            class Pump:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.stats = {}
                    self._t = threading.Thread(target=self._work)

                def _work(self):
                    self.stats["n"] = 1  # thread side: NO lock

                async def handle(self):
                    with self._lock:
                        self.stats["n"] = 0  # loop side: locked

                def stop(self):
                    self._t.join()
            """
        },
    )
    assert [f.rule for f in findings] == ["THRD001"]
    assert "self.stats" in findings[0].message
    assert "thread" in findings[0].message


def test_thrd001_negative_both_sides_locked_or_single_context(tmp_path):
    findings = _paths_findings(
        tmp_path,
        {
            "pump.py": """
            import threading

            class Pump:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.stats = {}
                    self.loop_only = {}
                    self._t = threading.Thread(target=self._work)

                def _work(self):
                    with self._lock:
                        self.stats["n"] = 1

                async def handle(self):
                    with self._lock:
                        self.stats["n"] = 0
                    self.loop_only["n"] = 2  # one context only: fine

                def stop(self):
                    self._t.join()
            """
        },
    )
    assert [f.rule for f in findings] == []


def test_thrd001_positive_module_global(tmp_path):
    findings = _paths_findings(
        tmp_path,
        {
            "telemetry.py": """
            import threading

            _count = 0

            def _bump():
                global _count
                _count += 1  # runs on sender threads AND the loop

            async def on_frame():
                _bump()

            _t = threading.Thread(target=_bump)
            """
        },
    )
    assert [f.rule for f in findings] == ["THRD001"]
    assert "_count" in findings[0].message


def test_thrd002_positive_unsnapshotted_iteration(tmp_path):
    findings = _paths_findings(
        tmp_path,
        {
            "collect.py": """
            import threading

            class Stats:
                def __init__(self):
                    self.rows = {}
                    self._t = threading.Thread(target=self._work)

                def _work(self):
                    self.rows["x"] = 1

                async def snapshot(self):
                    out = []
                    for k in self.rows:  # loop side iterates, no snapshot
                        out.append(k)
                    return out

                def stop(self):
                    self._t.join()
            """
        },
    )
    assert [f.rule for f in findings] == ["THRD002"]
    assert "list(" in findings[0].message


def test_thrd002_negative_list_snapshot(tmp_path):
    findings = _paths_findings(
        tmp_path,
        {
            "collect.py": """
            import threading

            class Stats:
                def __init__(self):
                    self.rows = {}
                    self._t = threading.Thread(target=self._work)

                def _work(self):
                    self.rows["x"] = 1

                async def snapshot(self):
                    return [k for k in list(self.rows)]  # PR-9 fix shape

                def stop(self):
                    self._t.join()
            """
        },
    )
    assert [f.rule for f in findings] == []


def test_thrd001_sync_anywhere_stays_silent(tmp_path):
    """A function the classifier cannot tie to a thread target or coroutine
    must not fire — unresolvable callees miss findings, never invent them."""
    findings = _paths_findings(
        tmp_path,
        {
            "plain.py": """
            class Plain:
                def __init__(self):
                    self.stats = {}

                def poke(self):
                    self.stats["n"] = 1

                async def handle(self):
                    self.stats["n"] = 0
            """
        },
    )
    assert [f.rule for f in findings] == []


# -- v2: DET001/002/003 (determinism discipline) -------------------------------


def det_rules_of(source: str) -> list[str]:
    findings = analyze_source(
        textwrap.dedent(source),
        "control/sim.py",
        config=ArlintConfig(det_modules=("control/sim.py",)),
    )
    return [f.rule for f in findings]


def test_det001_positive_wall_clock_reads():
    src = """
    import time
    from datetime import datetime

    def stamp():
        return time.time(), datetime.now()
    """
    assert det_rules_of(src) == ["DET001", "DET001"]


def test_det001_negative_injected_clock_and_perf_counter():
    src = """
    import time

    def run(clock=time.monotonic):
        start = time.perf_counter()  # wall-cost measuring: exempt
        return clock(), time.perf_counter() - start
    """
    assert det_rules_of(src) == []


def test_det001_gated_on_det_modules():
    src = "import time\ndef f():\n    return time.time()\n"
    assert analyze_source(src, "control/other.py", config=ArlintConfig(
        det_modules=("control/sim.py",))) == []


def test_det002_positive_global_rng():
    src = """
    import random
    import numpy as np

    def jitter():
        return random.random() + np.random.rand()
    """
    assert det_rules_of(src) == ["DET002", "DET002"]


def test_det002_negative_seeded_construction():
    src = """
    import random
    import numpy as np

    def make(seed):
        return random.Random(seed), np.random.default_rng(seed)
    """
    assert det_rules_of(src) == []


def test_det003_positive_set_iteration_shapes():
    src = """
    def walk(ids: set):
        for i in ids:
            yield i
        emitted = [i for i in ids]
        # list() only freezes the nondeterministic order — still flagged
        for i in list(ids):
            yield i
    """
    rules = det_rules_of(src)
    assert rules == ["DET003", "DET003", "DET003"]


def test_det003_negative_sorted_and_order_insensitive():
    src = """
    def walk(ids: set):
        for i in sorted(ids):
            yield i
        total = sum(i for i in ids)  # order-insensitive consumer
        other = {i + 1 for i in ids}  # set-to-set: no observable order
        return total, other
    """
    assert det_rules_of(src) == []


# -- v2: WIRE002 (version-skew contract) ---------------------------------------

_WIRE_V2_BASE = """
import dataclasses

@dataclasses.dataclass
class Ping:
    seq: int

@dataclasses.dataclass
class Pong:
    seq: int

_TAGS = {Ping: 1, Pong: 2}

def _encode_parts(msg):
    if isinstance(msg, Ping):
        return b"\\x01"
    if isinstance(msg, Pong):
        return b"\\x02"

def decode(buf):
    tag = buf[0]
    if tag == 1:
        return Ping(0)
    if tag == 2:
        return Pong(0)

def handle(msg):
    if isinstance(msg, Ping):
        return
    if isinstance(msg, Pong):
        return
"""


def test_wire002_positive_exact_consumed_length(tmp_path):
    src = _WIRE_V2_BASE + textwrap.dedent(
        """
        def decode_frame(buf):
            pos = 1
            if pos != len(buf):
                raise ValueError("trailing bytes")
            return decode(buf)
        """
    )
    findings = _paths_findings(
        tmp_path, {"wire.py": src}, rules=("WIRE002",)
    )
    assert [f.rule for f in findings] == ["WIRE002"]
    assert "trailing bytes" in findings[0].message


def test_wire002_negative_upper_bound_and_emptiness(tmp_path):
    src = _WIRE_V2_BASE + textwrap.dedent(
        """
        def decode_frame(buf):
            pos = 1
            if len(buf) == 0:
                raise ValueError("empty")
            assert pos <= len(buf)
            return decode(buf)
        """
    )
    findings = _paths_findings(
        tmp_path, {"wire.py": src}, rules=("WIRE002",)
    )
    assert [f.rule for f in findings] == []


def test_wire002_positive_defaultless_after_defaulted(tmp_path):
    src = _WIRE_V2_BASE.replace(
        "class Pong:\n    seq: int",
        "class Pong:\n    seq: int = 0\n    epoch: int",
    )
    findings = _paths_findings(
        tmp_path, {"wire.py": src}, rules=("WIRE002",)
    )
    assert [f.rule for f in findings] == ["WIRE002"]
    assert "trailing-with-default" in findings[0].message


def test_wire002_positive_tags_not_contiguous(tmp_path):
    src = _WIRE_V2_BASE.replace('Pong: 2', 'Pong: 3')
    findings = _paths_findings(
        tmp_path, {"wire.py": src}, rules=("WIRE002",)
    )
    assert [f.rule for f in findings] == ["WIRE002"]
    assert "contiguous" in findings[0].message


def test_wire002_positive_owned_range_violated(tmp_path):
    gossip = """
    import dataclasses

    @dataclasses.dataclass
    class Rumor:
        inc: int
    """
    findings = _paths_findings(
        tmp_path,
        {
            "wire.py": _WIRE_V2_BASE.replace(
                '_TAGS = {Ping: 1, Pong: 2}',
                '_TAGS = {Ping: 1, Pong: 2, Rumor: 3}',
            )
            + "\ndef _encode_rumor(msg):\n"
            + "    if isinstance(msg, Rumor):\n        return b'\\x03'\n",
            "gossip.py": gossip,
        },
        wire_owned=(("gossip.py", 2, 3),),
        rules=("WIRE002",),
    )
    assert [f.rule for f in findings] == ["WIRE002"]
    assert "wire-owned range" in findings[0].message


def test_wire002_owned_range_satisfied(tmp_path):
    gossip = """
    import dataclasses

    @dataclasses.dataclass
    class Rumor:
        inc: int
    """
    findings = _paths_findings(
        tmp_path,
        {
            "wire.py": _WIRE_V2_BASE.replace(
                '_TAGS = {Ping: 1, Pong: 2}',
                '_TAGS = {Ping: 1, Pong: 2, Rumor: 3}',
            ),
            "gossip.py": gossip,
        },
        wire_owned=(("gossip.py", 3, 3),),
        rules=("WIRE002",),
    )
    assert [f.rule for f in findings] == []


# -- v2: LIFE001 (teardown completeness) ---------------------------------------


def test_life001_positive_unreferenced_and_no_teardown():
    src = """
    import threading

    class Leaky:
        def start(self):
            self._t = threading.Thread(target=self._run)

        def stop(self):
            pass  # never references self._t

    class Orphan:
        def start(self):
            self._task = observed_task(self._run())
    """
    rules = rules_of(src)
    assert rules == ["LIFE001", "LIFE001"]


def test_life001_negative_referenced_or_dynamic_teardown():
    src = """
    import threading

    class Joined:
        def start(self):
            self._t = threading.Thread(target=self._run)

        def stop(self):
            self._t.join()

    class Dynamic:
        def start(self):
            self._poll_task = observed_task(self._poll())
            self._lease_task = observed_task(self._lease())

        async def stop(self):
            for attr in ("_poll_task", "_lease_task"):
                task = getattr(self, attr)
                if task is not None:
                    task.cancel()
    """
    assert rules_of(src) == []


# -- v2: OBS001 (doc drift, both directions) -----------------------------------


_OBS_DOC = """
# metrics

| name | type | meaning |
|---|---|---|
| `pump.frames` | counter | frames pumped |
| `pump.stage.<stage>` | counter | per-stage |
| `pull.side` | collector | pull-time rows, no creation site |
"""


def _obs_findings(tmp_path, source: str, doc: str = _OBS_DOC):
    (tmp_path / "OBS.md").write_text(textwrap.dedent(doc))
    return _paths_findings(
        tmp_path,
        {"a.py": source, "b.py": "x = 1\n"},
        obs_doc="OBS.md",
        rules=("OBS001",),
    )


def test_obs001_forward_positive_undocumented_metric(tmp_path):
    findings = _obs_findings(
        tmp_path,
        """
        def arm(metrics, stage):
            metrics.counter("pump.frames").inc()
            metrics.counter(f"pump.stage.{stage}").inc()
            metrics.gauge("pump.depth").set(1)  # not in the doc
        """,
    )
    assert [(f.rule, f.path) for f in findings] == [("OBS001", "a.py")]
    assert "pump.depth" in findings[0].message


def test_obs001_forward_fstring_matches_placeholder_row(tmp_path):
    findings = _obs_findings(
        tmp_path,
        """
        def arm(metrics, stage):
            metrics.counter(f"pump.stage.{stage}").inc()
            metrics.counter("pump.frames").inc()
        """,
    )
    assert [f.rule for f in findings] == []


def test_obs001_reverse_positive_dead_doc_row(tmp_path):
    findings = _obs_findings(
        tmp_path,
        """
        def arm(metrics, stage):
            metrics.counter("pump.frames").inc()
            metrics.counter(f"pump.stage.{stage}").inc()
        """,
        doc=_OBS_DOC + "| `pump.retired` | counter | gone from the code |\n",
    )
    assert [(f.rule, f.path) for f in findings] == [("OBS001", "OBS.md")]
    assert "pump.retired" in findings[0].message
    assert "collector" not in findings[0].line_content


def test_obs001_collector_rows_exempt_from_reverse(tmp_path):
    findings = _obs_findings(
        tmp_path,
        """
        def arm(metrics, stage):
            metrics.counter("pump.frames").inc()
            metrics.counter(f"pump.stage.{stage}").inc()
        """,
    )
    # `pull.side` has no creation site but is marked collector: no finding
    assert [f.rule for f in findings] == []


def test_obs001_inactive_without_obs_doc_config(tmp_path):
    findings = _paths_findings(
        tmp_path,
        {"a.py": 'def f(m):\n    m.counter("no.doc.at_all").inc()\n'},
        rules=("OBS001",),
    )
    assert findings == []


# -- v2: seeded violations in real sources, one per family --------------------


def test_seeded_thread_race_in_real_transport_source(tmp_path):
    """Appending a PR-9-shaped unlocked cross-context mutation to a COPY of
    control/remote.py is caught by the full pipeline."""
    source = (PKG_DIR / "control" / "remote.py").read_text()
    seeded = source + textwrap.dedent(
        """
        class _SeededPump:
            def __init__(self):
                self.backoff = {}
                self._t = threading.Thread(target=self._work)

            def _work(self):
                self.backoff["ep"] = 1.0

            async def on_frame(self):
                self.backoff["ep"] = 0.0

            def stop(self):
                self._t.join()
        """
    )
    (tmp_path / "remote.py").write_text(seeded)
    findings = analyze_paths(
        [tmp_path], ArlintConfig(rules=("THRD001",)), root=tmp_path
    )
    assert {f.rule for f in findings} == {"THRD001"}
    assert all("_Seeded" in f.message or f.line > 1 for f in findings)


def test_seeded_wall_clock_in_real_gossip_source(tmp_path):
    """gossip.py is a declared det-module: a seeded time.time() read fails
    the same gate the dynamic byte-identical chaos replays pin."""
    source = (PKG_DIR / "control" / "gossip.py").read_text()
    cfg = ArlintConfig(det_modules=("gossip.py",), rules=("DET001",))
    (tmp_path / "gossip.py").write_text(source)
    assert analyze_paths([tmp_path], cfg, root=tmp_path) == []
    (tmp_path / "gossip.py").write_text(
        source + "\n\ndef _seeded_stamp():\n    return time.time()\n"
    )
    findings = analyze_paths([tmp_path], cfg, root=tmp_path)
    assert [f.rule for f in findings] == ["DET001"]


def test_seeded_exact_length_in_real_wire_source(tmp_path):
    """A '== len(buf)' consumed-length assertion seeded into a COPY of
    control/wire.py violates the trace-trailer skew contract statically."""
    source = (PKG_DIR / "control" / "wire.py").read_text()
    seeded = source + textwrap.dedent(
        """
        def _seeded_decode_strict(buf):
            pos = 4
            if pos != len(buf):
                raise ValueError("trailing bytes are the skew contract")
        """
    )
    (tmp_path / "wire.py").write_text(seeded)
    findings = analyze_paths(
        [tmp_path], ArlintConfig(rules=("WIRE002",)), root=tmp_path
    )
    assert [f.rule for f in findings] == ["WIRE002"]


def test_seeded_leaked_thread_in_real_transport_source():
    """A spawned-but-never-torn-down Thread seeded into control/remote.py
    source is the literal PR-13 sender-thread leak shape."""
    source = (PKG_DIR / "control" / "remote.py").read_text()
    seeded = source + textwrap.dedent(
        """
        class _SeededSpawner:
            def start(self):
                self._pump_thread = threading.Thread(target=self._run)

            def stop(self):
                pass
        """
    )
    rules = [f.rule for f in analyze_source(seeded, "remote.py")]
    assert rules == ["LIFE001"]


def test_seeded_undocumented_metric_in_real_source(tmp_path):
    """A metric created under a name OBSERVABILITY.md does not document
    fails the forward drift check against the real doc."""
    source = (PKG_DIR / "obs" / "metrics.py").read_text()
    seeded = source + (
        "\n_SEEDED = REGISTRY.counter('transport.seeded_bogus_name')\n"
    )
    (tmp_path / "metrics.py").write_text(seeded)
    findings = analyze_paths(
        [tmp_path],
        ArlintConfig(
            obs_doc=str(REPO_ROOT / "OBSERVABILITY.md"), rules=("OBS001",)
        ),
        root=tmp_path,
    )
    assert [f.rule for f in findings] == ["OBS001"]
    assert "transport.seeded_bogus_name" in findings[0].message


# -- v2: analyzer output is itself deterministic -------------------------------


def test_analyzer_output_ordering_is_pinned(tmp_path):
    """Findings sort by (path, line, rule, message) and two runs agree
    exactly — the analyzer's own output obeys the replay discipline it
    enforces."""
    sources = {
        "b_mod.py": """
        import time, asyncio
        async def f(c):
            time.sleep(1)
            asyncio.create_task(c)
        """,
        "a_mod.py": """
        import time
        async def g():
            time.sleep(2)
        """,
    }
    first = _paths_findings(tmp_path, sources)
    second = analyze_paths([tmp_path], ArlintConfig(), root=tmp_path)
    keyed = [(f.path, f.line, f.rule, f.message) for f in first]
    assert keyed == sorted(keyed)
    assert first == second
    assert [f.path for f in first] == ["a_mod.py", "b_mod.py", "b_mod.py"]


# -- v2: CLI output modes ------------------------------------------------------


def test_cli_github_format(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    r = _run_cli(str(bad), "--format=github", "--no-baseline")
    assert r.returncode == 1
    line = r.stdout.splitlines()[0]
    assert line.startswith("::error file=")
    assert "line=3" in line and "title=ASYNC001" in line
    assert "\n" not in line.split("::", 2)[2] or "%0A" in line
    bad.write_text("async def f(): ...\n")
    r = _run_cli(str(bad), "--format=github", "--no-baseline")
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_github_format_escapes_newlines(tmp_path):
    from akka_allreduce_tpu.analysis.__main__ import _gh_escape

    assert _gh_escape("a\nb%c\rd") == "a%0Ab%25c%0Dd"


def test_cli_sarif_output(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    out = tmp_path / "lint.sarif"
    r = _run_cli(str(bad), "--sarif", str(out), "--no-baseline")
    assert r.returncode == 1  # exit-code contract unchanged by --sarif
    log = json.loads(out.read_text())
    assert log["version"] == "2.1.0"
    run = log["runs"][0]
    assert run["tool"]["driver"]["name"] == "arlint"
    results = run["results"]
    assert len(results) == 1 and results[0]["ruleId"] == "ASYNC001"
    region = results[0]["locations"][0]["physicalLocation"]["region"]
    assert region["startLine"] == 3
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "THRD001" in rule_ids and "ASYNC001" in rule_ids
    # clean run still writes a (result-free) log and exits 0
    bad.write_text("async def f(): ...\n")
    r = _run_cli(str(bad), "--sarif", str(out), "--no-baseline")
    assert r.returncode == 0
    assert json.loads(out.read_text())["runs"][0]["results"] == []


def test_cli_json_conflicts_with_other_format(tmp_path):
    bad = tmp_path / "ok.py"
    bad.write_text("x = 1\n")
    r = _run_cli(str(bad), "--json", "--format=github")
    assert r.returncode == 2
    assert "conflicts" in r.stderr


def test_cli_widened_surface_matches_make_lint():
    """The exact widened `make lint` surface (package + entry shims + test
    worker helpers) exits 0 on the shipped tree."""
    lint_paths = ["akka_allreduce_tpu/", "chip_smoke.py"] + sorted(
        str(p.relative_to(REPO_ROOT)) for p in (REPO_ROOT / "tests").glob("*_worker.py")
    )
    assert lint_paths[2:], "worker helpers must exist (surface satellite)"
    r = _run_cli(*lint_paths)
    assert r.returncode == 0, r.stdout + r.stderr
