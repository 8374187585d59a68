"""Logging, metrics, and timing utilities."""

from akka_allreduce_tpu.utils.metrics import MetricsLogger  # noqa: F401
from akka_allreduce_tpu.utils.compile_cache import (  # noqa: F401
    enable_compile_cache,
)
from akka_allreduce_tpu.utils.verify import (  # noqa: F401
    assert_replica_consistent,
    assert_trainer_replicas,
)
