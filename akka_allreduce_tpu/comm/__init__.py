"""ICI data plane: the XLA-collective replacement for the reference's L0-L2.

The reference moves float chunks as serialized actor messages over Netty TCP and
sums them in a JVM loop (SURVEY.md §4.2 hot path). Here the whole scatter-reduce-
allgather round is ONE compiled XLA collective over the ICI mesh: payloads stay
in HBM, the reduction executor is XLA's AllReduce, and threshold semantics are
carried by a validity mask fused into the same collective
(sum = psum(x * valid), count = psum(valid); consumer divides — SURVEY.md §8.1
step 3, BASELINE.json:5).
"""

from akka_allreduce_tpu.comm.allreduce import (  # noqa: F401
    AllreduceResult,
    build_threshold_allreduce,
    masked_psum,
    threshold_allreduce,
)
