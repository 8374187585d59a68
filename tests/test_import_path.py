"""What a process start imports (PERF.md section 5, set-up).

``orbax.checkpoint`` (with ``google.cloud.logging`` and ``tensorstore`` under
it) is seconds of every start and only ``TrainerCheckpointer`` uses it, so no
way into the package may load it; the checkpointer's construction does, once
a process. Every case is a fresh interpreter (``run_fresh``, conftest.py).
"""

import pytest

ENTRIES = {
    # the training cells, every train-* command, soak
    "train": "import akka_allreduce_tpu.train",
    # a configuration-built decoder before its trainer
    "hybrid_decoder": "import akka_allreduce_tpu.models.hybrid_decoder",
    # the allreduce cell: never loads ``train``
    "allreduce": (
        "from akka_allreduce_tpu.comm.allreduce import "
        "build_threshold_allreduce"
    ),
}

_CONSTRUCT_TWICE = """
    from akka_allreduce_tpu.obs import metrics
    from akka_allreduce_tpu.train import TrainerCheckpointer
    assert "orbax.checkpoint" not in sys.modules
    TrainerCheckpointer(sys.argv[1] + "/a").close()
    first = metrics.gauge("checkpoint.orbax_import_s").value
    TrainerCheckpointer(sys.argv[1] + "/b").close()
    extra = {"first_import_s": first}
"""


@pytest.mark.parametrize("case", [*ENTRIES, "checkpointer"])
def test_orbax_loads_with_the_checkpointer_only(case, tmp_path, run_fresh):
    if case in ENTRIES:
        got = run_fresh(ENTRIES[case])
        assert got["deferred"] == []
        assert got["import_s"] is None and got["spans"] == 0
        return
    got = run_fresh(_CONSTRUCT_TWICE, str(tmp_path))
    assert "orbax.checkpoint" in got["deferred"]
    assert got["import_s"] > 0
    # loaded once: the second construction neither imports nor re-times
    assert got["import_s"] == got["extra"]["first_import_s"]
    assert got["spans"] == 1
