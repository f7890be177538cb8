"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs here: each test lowers one kernel wrapper at real widths
(m = h = 256, bf = 8, bq = 128, thousands of filters) against a ``v5e:2x2``
topology description and asks the TPU compiler for the executable, so a
block layout, tiling or VMEM budget the chip would refuse fails in CI
instead of on the chip.  The topology is described inside a fixture (never
at import): only one process at a time may load the TPU compiler library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.kernels.box_lb import ops as box_ops
from repro.kernels.filter_mlp import ops as mlp_ops
from repro.kernels.l2_scan import ops as l2_ops

M = H = 256            # series length = filter hidden width
F = 4096               # filters: every leaf of a 1M-series DSTree qualifies
Q = 128                # one query tile


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("weight_dtype", ["float32", "bfloat16", "int8"])
def test_fused_filter_kernel_compiles(one_chip, weight_dtype):
    wd = jnp.dtype(weight_dtype)
    f32 = jnp.float32
    shapes = [((F, M, H), wd), ((F, H), f32), ((F, H), wd), ((F,), f32),
              ((F,), f32), ((F,), f32), ((Q, M), f32), ((F,), f32)]
    if weight_dtype == "int8":
        shapes += [((F,), f32), ((F,), f32)]

    def fn(*a):
        return mlp_ops.filter_predict_fused(*a, interpret=False)

    compiled = _compile(fn, one_chip, *shapes)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30


def test_per_filter_kernel_compiles(one_chip):
    f32 = jnp.float32
    _compile(lambda *a: mlp_ops.filter_predict(*a, interpret=False),
             one_chip, ((F, M, H), f32), ((F, H), f32), ((F, H), f32),
             ((F,), f32), ((Q, M), f32))


def test_slab_l2_pairwise_compiles(one_chip):
    # the build's local-query pass: 64 noisy queries per leaf of 256 rows
    _compile(lambda q, s: l2_ops.slab_l2(q, s, "pairwise", interpret=False),
             one_chip, ((64, 64, M), jnp.float32),
             ((64, 256, M), jnp.float32))


def test_pairwise_l2_compiles(one_chip):
    # the build's all-leaves pass: a query batch against 16 leaves' rows
    _compile(lambda q, s: l2_ops.pairwise_l2(q, s, interpret=False),
             one_chip, ((200, M), jnp.float32), ((16 * 256, M), jnp.float32))


def test_box_lb_compiles(one_chip):
    # DSTree EAPCA boxes: 8 segments × (mean, std) coordinates
    _compile(lambda q, lo, hi: box_ops.box_lb(q, lo, hi, interpret=False),
             one_chip, ((Q, 16), jnp.float32), ((8192, 16), jnp.float32),
             ((8192, 16), jnp.float32))


def test_sharded_search_fits_four_chips(topo):
    """The per-query-offset shard body on a 1×4 mesh at 1M × 256 (4096
    leaves, 1024 slots per shard, a filter on every leaf): it compiles, and
    each chip's share fits its 16 GB."""
    from repro.core import distributed
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    S, P, R, Q, L = 4, 1024, 256, 64, 4096
    f32, i32 = jnp.float32, jnp.int32
    by_shard = NamedSharding(mesh, PartitionSpec("model"))
    replicated = NamedSharding(mesh, PartitionSpec())

    def sd(shape, dt=f32, sh=by_shard):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    sharded = distributed.ShardedLeaFi(
        series=sd((S, P * R + R, M)), leaf_start=sd((S, P), i32),
        leaf_size=sd((S, P), i32), lb_lo=sd((S, P, 16)),
        lb_hi=sd((S, P, 16)), w1=sd((S, P, M, H)), b1=sd((S, P, H)),
        w2=sd((S, P, H)), b2=sd((S, P)), y_mean=sd((S, P)),
        y_std=sd((S, P)), offsets=sd((S, P)),
        has_filter=sd((S, P), jnp.bool_), max_leaf=R, length=M,
        kind="dstree", qscale=np.ones(16, np.float32),
        leaf_global=sd((S, P), i32))
    run, *_ = distributed.make_distributed_search(
        mesh, sharded, per_query_offsets=True, dist_impl="matmul")
    compiled = run.func.lower(
        run.args[0], sd((Q, M), sh=replicated), sd((Q, L), sh=replicated),
        sd((Q,), sh=replicated)).compile()
    mem = compiled.memory_analysis()
    per_chip = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert per_chip < 16 * 10 ** 9, per_chip
    assert "all-reduce" in compiled.as_text()
