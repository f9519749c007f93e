"""Main-path Pallas kernels compile for a described TPU v5e.

Every kernel the build and the search call is lowered and compiled for
a v5e chip that is described, not attached (jax.experimental.topologies):
the TPU compiler runs here and refuses what the chip would refuse —
Mosaic lowering failures, scoped-VMEM overruns, HBM overruns. Shapes are
the real ones: a 70,000-row corpus, C=40 join candidates, k=20, feature
widths 128 and 784, 1,024-query blocks of W=40 candidates. The local
join's gathered tile goes through the kernel ``DescentConfig.join_chunk``
rows at a time (core/nn_descent.py), so that is the row count its
kernels compile at.

This is the only file that describes the chip: the topology is built in
a module-scoped fixture (only the worker that runs these tests loads the
TPU library), and the persistent compile cache is off around the
compiles (an entry written for a described chip cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.nn_descent import DescentConfig
from repro.kernels.knn_join import (
    knn_join_dists_blocked,
    knn_join_select_blocked,
)
from repro.kernels.knn_merge import (
    knn_compact_blocked,
    knn_merge_blocked,
    knn_merge_rows_blocked,
)
from repro.kernels.knn_search import knn_search_dists_blocked
from repro.kernels.l2_blocked import pairwise_sq_l2_blocked
from repro.kernels.l2_quant import (
    knn_join_dists_bf16_blocked,
    knn_join_dists_q8_blocked,
    knn_search_dists_bf16_blocked,
    knn_search_dists_q8_blocked,
)

N, C, K, NQ, W = 70_000, 40, 20, 1_024, 40
MERGE_C = 3 * K                       # DescentConfig.merge_k
JOIN_ROWS = DescentConfig().join_chunk
f32, i32, i8, bf16 = jnp.float32, jnp.int32, jnp.int8, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(scope="module")
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dp", [128, 784])
def test_join_dists(spec, no_cache, dp):
    _compile(lambda x, x2, ids: knn_join_dists_blocked(x, x2, ids, cn=C // 2),
             spec((JOIN_ROWS, C, dp), f32), spec((JOIN_ROWS, C), f32),
             spec((JOIN_ROWS, C), i32))


@pytest.mark.parametrize("dp", [128, 784])
def test_join_dists_bf16(spec, no_cache, dp):
    _compile(lambda x, x2, ids: knn_join_dists_bf16_blocked(
        x, x2, ids, cn=C // 2),
        spec((JOIN_ROWS, C, dp), bf16), spec((JOIN_ROWS, C), f32),
        spec((JOIN_ROWS, C), i32))


@pytest.mark.parametrize("dp", [128, 784])
def test_join_dists_q8(spec, no_cache, dp):
    _compile(lambda x, s, x2, ids: knn_join_dists_q8_blocked(
        x, s, x2, ids, cn=C // 2),
        spec((JOIN_ROWS, C, dp), i8), spec((JOIN_ROWS, C), f32),
        spec((JOIN_ROWS, C), f32), spec((JOIN_ROWS, C), i32))


@pytest.mark.parametrize("dp", [128, 784])
def test_search_dists(spec, no_cache, dp):
    _compile(knn_search_dists_blocked,
             spec((NQ, dp), f32), spec((NQ,), f32), spec((NQ, W, dp), f32),
             spec((NQ, W), f32), spec((NQ, W), i32))


@pytest.mark.parametrize("dp", [128, 784])
def test_search_dists_bf16(spec, no_cache, dp):
    _compile(knn_search_dists_bf16_blocked,
             spec((NQ, dp), bf16), spec((NQ,), f32),
             spec((NQ, W, dp), bf16), spec((NQ, W), f32),
             spec((NQ, W), i32))


@pytest.mark.parametrize("dp", [128, 784])
def test_search_dists_q8(spec, no_cache, dp):
    _compile(knn_search_dists_q8_blocked,
             spec((NQ, dp), i8), spec((NQ,), f32), spec((NQ,), f32),
             spec((NQ, W, dp), i8), spec((NQ, W), f32), spec((NQ, W), f32),
             spec((NQ, W), i32))


@pytest.mark.parametrize("dp", [128, 784])
def test_pairwise_sq_l2(spec, no_cache, dp):
    _compile(pairwise_sq_l2_blocked, spec((NQ, dp), f32), spec((N, dp), f32))


@pytest.mark.parametrize("width,c", [
    (2 * C * C, MERGE_C),     # build: (2C sources x C pairs) per receiver
    (K * K, 6 * K),           # polish: the k*k neighbors-of-neighbors row
])
def test_join_select(spec, no_cache, width, c):
    _compile(lambda gd, gi, kth: knn_join_select_blocked(gd, gi, kth, c=c),
             spec((JOIN_ROWS, width), f32), spec((JOIN_ROWS, width), i32),
             spec((JOIN_ROWS,), f32))


@pytest.mark.parametrize("k,c", [(K, MERGE_C), (128, 128), (256, 256)])
def test_merge(spec, no_cache, k, c):
    # (20, 60): the build merge; (128, 128) and (256, 256): the search
    # pool merge at beam 128 and at chip_smoke.py's beam 256
    _compile(knn_merge_blocked, spec((N, k), f32), spec((N, k), i32),
             spec((N, c), f32), spec((N, c), i32))


def test_merge_rows(spec, no_cache):
    f = 4_096
    _compile(knn_merge_rows_blocked, spec((N, K), f32), spec((N, K), i32),
             spec((f,), i32), spec((f, MERGE_C), f32),
             spec((f, MERGE_C), i32))


def test_compact(spec, no_cache):
    _compile(knn_compact_blocked, spec((N, K), f32), spec((N, K), i32),
             spec((N, K), jnp.bool_))
