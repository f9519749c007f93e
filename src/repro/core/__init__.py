"""The paper's contribution: fast K-NN-graph construction (NN-Descent with
turbosampling selection, greedy memory reordering, and blocked distance
evaluation), single-chip and mesh-sharded."""
from repro.core.faults import (
    FaultPlan,
    FaultSpec,
    InjectedFault,
    poison_batch,
)
from repro.core.graph_search import SearchConfig, SearchStats, graph_search
from repro.core.heap import NeighborLists
from repro.core.nn_descent import (
    DescentConfig,
    DescentStats,
    build_knn_graph,
    nn_descent_iteration,
)
from repro.core.online import (
    MutableKNNStore,
    OnlineConfig,
    ensure_router,
    knn_delete,
    knn_insert,
)
from repro.core.persist import (
    SnapshotError,
    SnapshotWriter,
    latest_snapshot,
    restore_store,
    snapshot_store,
)
from repro.core.quantize import (
    QuantizedStore,
    dequantize,
    quantize_corpus,
    quantize_sym_int8,
)
from repro.core.recall import brute_force_knn, distance_recall, recall_at_k
from repro.core.reorder import (
    apply_permutation,
    greedy_reorder,
    locality_stats,
    window_cluster_purity,
)
from repro.core.router import Router, RouterConfig, build_router

__all__ = [
    "DescentConfig",
    "DescentStats",
    "FaultPlan",
    "FaultSpec",
    "InjectedFault",
    "MutableKNNStore",
    "NeighborLists",
    "OnlineConfig",
    "QuantizedStore",
    "Router",
    "RouterConfig",
    "SearchConfig",
    "SearchStats",
    "SnapshotError",
    "SnapshotWriter",
    "apply_permutation",
    "brute_force_knn",
    "build_knn_graph",
    "build_router",
    "dequantize",
    "distance_recall",
    "ensure_router",
    "quantize_corpus",
    "quantize_sym_int8",
    "graph_search",
    "greedy_reorder",
    "knn_delete",
    "knn_insert",
    "latest_snapshot",
    "locality_stats",
    "nn_descent_iteration",
    "poison_batch",
    "recall_at_k",
    "restore_store",
    "snapshot_store",
    "window_cluster_purity",
]
