# The paper's compute hot-spots as Pallas TPU kernels:
#   l2_blocked      — §3.3 blocked distance evaluations (MXU tiling)
#   l2_quant        — §3.3 at int8/bf16 density: quantized candidate-
#                     scoring tiles of the two-stage distance path
#                     (fp32 kernels below stay the exact re-rank stage)
#   knn_join        — §3.3+§2 fused local join (pair tensor + per-receiver
#                     prefilter/top-C selection, no global pair sort)
#   knn_search      — query-time §3.3: blocked multi-expansion candidate
#                     distance tile for the fused batched graph search
#   knn_merge       — §2 bounded neighbor-list update, and the search's
#                     per-round pool insert
#   flash_attention — LM-stack attention hotspot (blocked online softmax)
# ops.py = jit'd dispatch wrappers, ref.py = pure-jnp oracles.
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.knn_join import (
    knn_join_dists_blocked,
    knn_join_select_blocked,
)
from repro.kernels.knn_merge import (
    knn_compact_rows_blocked,
    knn_merge_blocked,
    knn_merge_rows_blocked,
    knn_pool_insert_blocked,
)
from repro.kernels.knn_search import knn_search_dists_blocked
from repro.kernels.l2_blocked import pairwise_sq_l2_blocked
from repro.kernels.l2_quant import (
    knn_join_dists_bf16_blocked,
    knn_join_dists_q8_blocked,
    knn_search_dists_bf16_blocked,
    knn_search_dists_q8_blocked,
)

__all__ = [
    "ops",
    "ref",
    "flash_attention",
    "knn_compact_rows_blocked",
    "knn_join_dists_bf16_blocked",
    "knn_join_dists_blocked",
    "knn_join_dists_q8_blocked",
    "knn_join_select_blocked",
    "knn_merge_blocked",
    "knn_merge_rows_blocked",
    "knn_pool_insert_blocked",
    "knn_search_dists_bf16_blocked",
    "knn_search_dists_blocked",
    "knn_search_dists_q8_blocked",
    "pairwise_sq_l2_blocked",
]
