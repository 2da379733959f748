"""Real-size compiles of the main-path kernels for a described TPU v5e.

No chip is attached: `topologies.get_topology_desc` describes a v5e 2x2
host, and each kernel is lowered from ShapeDtypeStructs and compiled by
the TPU compiler, which refuses what the chip would refuse (tiling, ops,
memory). Shapes are BASELINE config #2's: 2^20 wildcard routes (table
capacity 2^21, hash slots 2^21), 1024-topic batches, the engine's
max_hits. The topology is described only inside the fixture: one process
at a time may load the TPU library, and pytest-xdist workers import
every test file.
"""

import functools
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

HBM_BYTES = 16 * 10**9  # one v5e chip
ROUTES = 1 << 20
CAPACITY = 2 * ROUTES  # FilterTable capacity at 2^20 routes
SLOTS = 2 * ROUTES  # hash slots (fp, bucket) at 2^20 routes
BUCKET_W = 4
CLASSES = 8
BATCH = 1024
MAX_HITS = max(1024, 2 * BATCH)  # Router.match_hash_begin's sizing


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", params=[(2, 2), (1, 4)], ids=["dp2xsub2", "sub4"])
def mesh(topo, request):
    # (1, 4) is what `parallel.enable, dp=1, sub=4` boots on the 2x2 host
    from emqx_tpu.parallel.mesh import DP_AXIS, SUB_AXIS

    return Mesh(
        np.asarray(topo.devices).reshape(request.param), (DP_AXIS, SUB_AXIS)
    )


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # a compile for a described chip cannot be read back without one
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def meta_shapes(sh):
    from emqx_tpu.ops.hash_index import ClassMeta

    return ClassMeta(
        sds((CLASSES,), jnp.int32, sh),
        sds((CLASSES,), jnp.bool_, sh),
        sds((CLASSES,), jnp.bool_, sh),
        sds((CLASSES,), jnp.uint32, sh),
        sds((CLASSES,), jnp.bool_, sh),
    )


def slot_shapes(sh):
    from emqx_tpu.ops.hash_index import SlotArrays

    return SlotArrays(
        sds((SLOTS,), jnp.uint32, sh),
        sds((SLOTS,), jnp.int32, sh),
        sds((SLOTS // BUCKET_W,), jnp.uint32, sh),
    )


def topic_shapes(levels, ids_sh, row_sh):
    from emqx_tpu.ops.match import EncodedTopics

    return EncodedTopics(
        sds((BATCH, levels), jnp.int32, ids_sh),
        sds((BATCH,), jnp.int32, row_sh),
        sds((BATCH,), jnp.bool_, row_sh),
    )


def packed_shapes(levels, sh):
    from emqx_tpu.ops.match import PackedTopics

    return PackedTopics(sds((BATCH, levels + 2), jnp.int32, sh))


def filter_shapes(levels, words_sh, row_sh):
    from emqx_tpu.ops.table import EncodedFilters

    return EncodedFilters(
        sds((CAPACITY, levels), jnp.int32, words_sh),
        sds((CAPACITY,), jnp.int32, row_sh),
        sds((CAPACITY,), jnp.bool_, row_sh),
        sds((CAPACITY,), jnp.bool_, row_sh),
        sds((CAPACITY,), jnp.bool_, row_sh),
    )


def assert_fits(compiled):
    m = compiled.memory_analysis()
    used = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    assert 0 < used < HBM_BYTES, used


@pytest.mark.parametrize("levels", [8, 16])  # bench.py's #2 / Router's default
def test_match_ids_hash(one_chip, levels):
    from emqx_tpu.ops.hash_index import match_ids_hash

    compiled = match_ids_hash.lower(
        meta_shapes(one_chip),
        slot_shapes(one_chip),
        packed_shapes(levels, one_chip),
        max_hits=MAX_HITS,
    ).compile()
    assert_fits(compiled)
    # one packed topics operand, still named topics_ids (the benchmark's
    # kernel reader reads B and L from it)
    text = compiled.as_text()
    assert re.search(rf"topics_ids(\.\d+)?: s32\[{BATCH},{levels + 2}\]", text)
    assert "topics_lens" not in text and "topics_dollar" not in text


def test_resolve_fanout_100k(one_chip):
    from emqx_tpu.ops.fanout import resolve_fanout

    fan = 1 << 17  # pow2 >= a 100k-subscriber fan
    edges = 1 << 18
    compiled = resolve_fanout.lower(
        sds((CAPACITY,), jnp.int32, one_chip),
        sds((CAPACITY,), jnp.int32, one_chip),
        sds((edges,), jnp.int32, one_chip),
        sds((edges,), jnp.int32, one_chip),
        sds((8,), jnp.int32, one_chip),
        n_clients=fan,
        max_fan=fan,
    ).compile()
    assert_fits(compiled)


@pytest.mark.parametrize("kind", ["rows", "slots"])
def test_delta_scatter_sync(one_chip, kind):
    from emqx_tpu.models import router

    n_b, k, levels = 4, router.SYNC_BATCH_SIZE, 16
    batch = functools.partial(sds, sharding=one_chip)
    if kind == "rows":
        lowered = router._scatter_rows.lower(
            filter_shapes(levels, one_chip, one_chip),
            batch((n_b, k), jnp.int32),
            batch((n_b, k, levels), jnp.int32),
            batch((n_b, k), jnp.int32),
            batch((n_b, k), jnp.bool_),
            batch((n_b, k), jnp.bool_),
            batch((n_b, k), jnp.bool_),
        )
    else:
        lowered = router._scatter_slots.lower(
            slot_shapes(one_chip),
            batch((n_b, k), jnp.int32),
            batch((n_b, k), jnp.uint32),
            batch((n_b, k), jnp.int32),
            batch((n_b, k), jnp.uint32),
        )
    assert_fits(lowered.compile())


def test_sharded_dense_match(mesh):
    from emqx_tpu.parallel.sharded_match import make_sharded_kernels

    match_counts, _packed, _apply = make_sharded_kernels(mesh)
    compiled = match_counts.lower(
        filter_shapes(16, NamedSharding(mesh, P("sub", None)),
                      NamedSharding(mesh, P("sub"))),
        topic_shapes(16, NamedSharding(mesh, P("dp", None)),
                     NamedSharding(mesh, P("dp"))),
    ).compile()
    assert_fits(compiled)
    assert "all-reduce" in compiled.as_text()


def test_sharded_delta_apply(mesh):
    from emqx_tpu.parallel.sharded_match import make_sharded_kernels

    _mc, _mp, apply_delta = make_sharded_kernels(mesh)
    n_b, k, levels = 4, 1024, 16
    repl = NamedSharding(mesh, P())
    compiled = apply_delta.lower(
        filter_shapes(levels, NamedSharding(mesh, P("sub", None)),
                      NamedSharding(mesh, P("sub"))),
        sds((n_b, k), jnp.int32, repl),
        sds((n_b, k, levels), jnp.int32, repl),
        sds((n_b, k), jnp.int32, repl),
        sds((n_b, k), jnp.bool_, repl),
        sds((n_b, k), jnp.bool_, repl),
        sds((n_b, k), jnp.bool_, repl),
    ).compile()
    assert_fits(compiled)


def test_sharded_hash_match_and_combine(mesh):
    from emqx_tpu.parallel.sharded_match import make_sharded_hash_kernel

    kernel = make_sharded_hash_kernel(mesh, MAX_HITS)
    compiled = kernel.lower(
        meta_shapes(NamedSharding(mesh, P(None))),
        slot_shapes(NamedSharding(mesh, P("sub"))),
        topic_shapes(16, NamedSharding(mesh, P("dp", None)),
                     NamedSharding(mesh, P("dp"))),
    ).compile()
    assert_fits(compiled)
    hlo = compiled.as_text()
    assert "all-gather" in hlo and "all-reduce" in hlo
