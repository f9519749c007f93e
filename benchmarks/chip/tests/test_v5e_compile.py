"""Compiles of the cells' programs at the cells' real sizes for a
described (not attached) TPU v5e: the benchmark's own programs (the
corpus generator, the reference) and the program's programs that the
cells' windows run. Needs no chip; a few minutes on a CPU.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q \
        benchmarks/chip/tests/test_v5e_compile.py
"""
from __future__ import annotations

import dataclasses
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmarks.chip import data, reference  # noqa: E402

MNIST = dict(rows=70_000, dim=784, clusters=10, sep=4.0, transform="mnist")
AUDIO = dict(rows=54_387, dim=192, clusters=40, sep=2.0, transform="none")
HBM = 16e9


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import compilation_cache, topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM, used


def key_sds(chip):
    return sds((), jax.random.key(0).dtype, chip)


@pytest.mark.parametrize("cfg", [MNIST, AUDIO], ids=["mnist784", "audio192"])
def test_corpus(chip, cfg):
    kw = dict(n=cfg["rows"], d=cfg["dim"], clusters=cfg["clusters"],
              sep=cfg["sep"], transform=cfg["transform"])
    fits(data.corpus.lower(key_sds(chip), **kw).compile())


@pytest.mark.parametrize("n,d,k", [(70_000, 784, 20), (54_387, 192, 20),
                                   (60_000, 784, 10)],
                         ids=["mnist784.build", "audio192.build", "search"])
def test_reference(chip, n, d, k):
    q = sds((128, d), jnp.float32, chip)
    fits(reference._block.lower(
        q, sds((128,), jnp.int32, chip), sds((n, d), jnp.float32, chip),
        sds((n,), jnp.bool_, chip), k=k, m=2048).compile())
    fits(reference._pair.lower(
        sds((1024, d), jnp.float32, chip), sds((n, d), jnp.float32, chip),
        sds((1024, k), jnp.int32, chip)).compile())


@pytest.mark.parametrize("n,dp", [(70_000, 896), (54_387, 256)],
                         ids=["mnist784", "audio192"])
def test_build_programs(chip, n, dp):
    from repro.core import heap
    from repro.core.nn_descent import (DescentConfig, nn_descent_iteration,
                                       polish_iteration, rerank_lists)
    from repro.core.reorder import greedy_reorder
    k = 20
    cfg = DescentConfig(k=k, backend="pallas")
    x = sds((n, dp), jnp.float32, chip)
    x2 = sds((n,), jnp.float32, chip)
    nl = heap.NeighborLists(sds((n, k), jnp.float32, chip),
                            sds((n, k), jnp.int32, chip),
                            sds((n, k), jnp.bool_, chip))
    fits(nn_descent_iteration.lower(key_sds(chip), x, x2, nl, cfg).compile())
    fits(polish_iteration.lower(x, x2, nl, "pallas").compile())
    fits(greedy_reorder.lower(nl).compile())
    fits(rerank_lists.lower(x, nl).compile())
    # the control: the same iteration scoring on a bf16 mirror
    bf16 = dataclasses.replace(cfg, precision="bf16")
    fits(nn_descent_iteration.lower(key_sds(chip), x, x2, nl, bf16,
                                    mirror(chip, n, dp)).compile())


def mirror(chip, n, dp):
    """A bf16 corpus mirror (``quantize.QuantizedStore``); on a TPU the
    mirror is as wide as the fp32 layout."""
    from repro.core.quantize import QuantizedStore
    return QuantizedStore(sds((n, dp), jnp.bfloat16, chip),
                          sds((n,), jnp.float32, chip),
                          sds((n,), jnp.float32, chip))


@pytest.mark.parametrize("precision", ["f32", "bf16"],
                         ids=["batch", "control"])
def test_search_block(chip, precision):
    import importlib
    gs = importlib.import_module("repro.core.graph_search")
    n, dp, k, qb = 65_536, 896, 20, 256  # the store's capacity past 60,000
    cfg = gs.SearchConfig(beam=256, rounds=256, backend="pallas",
                          precision=precision)
    qstore = mirror(chip, n, dp) if precision == "bf16" else None
    fits(gs._search_block.lower(
        sds((n, dp), jnp.float32, chip), sds((n,), jnp.float32, chip),
        sds((n, k), jnp.int32, chip), sds((qb, dp), jnp.float32, chip),
        sds((qb,), jnp.float32, chip), sds((qb, 256), jnp.int32, chip),
        sds((n,), jnp.bool_, chip), None, qstore, k_out=10,
        cfg=cfg).compile())
