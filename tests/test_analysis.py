"""HLO stats parser: trip counts, flops, collective detection."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import hlo_stats


def test_scan_flops_count_trip_multiplied():
    W = jnp.zeros((256, 256), jnp.float32)

    def f_scan(x):
        def body(c, _):
            return c @ W, None
        out, _ = jax.lax.scan(body, x, None, length=8)
        return out

    def f_unroll(x):
        for _ in range(8):
            x = x @ W
        return x

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    expect = 2 * 256 ** 3 * 8
    for f in (f_scan, f_unroll):
        st = hlo_stats(jax.jit(f).lower(x).compile().as_text())
        assert st.flops == expect, (f.__name__, st.flops, expect)


def test_nested_scan_flops():
    W = jnp.zeros((128, 128), jnp.float32)

    def f(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ W, None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        out, _ = jax.lax.scan(outer, x, None, length=4)
        return out

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    st = hlo_stats(jax.jit(f).lower(x).compile().as_text())
    assert st.flops == 2 * 128 ** 3 * 12


def test_f32_projection_halves_bytes():
    W = jnp.zeros((512, 512), jnp.float32)
    f = lambda x: x @ W                                 # noqa: E731
    x = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    hlo = jax.jit(f).lower(x).compile().as_text()
    raw = hlo_stats(hlo).hbm_bytes
    proj = hlo_stats(hlo, f32_as_bf16=True).hbm_bytes
    assert abs(proj * 2 - raw) / raw < 1e-6


@pytest.mark.skipif(jax.device_count() != 1, reason="needs subprocess devices")
def test_collectives_detected_in_sharded_program():
    """Run in a subprocess with 8 host devices: a psum must show up as an
    all-reduce with correct byte attribution."""
    import subprocess
    import sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import sys
sys.path.insert(0, "src")
from repro.analysis import hlo_stats
mesh = jax.make_mesh((8,), ("d",), axis_types=(jax.sharding.AxisType.Auto,))
x = jax.ShapeDtypeStruct((1024, 512), jnp.float32)
w = jax.ShapeDtypeStruct((512, 256), jnp.float32)
f = lambda x, w: x @ w
c = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, "d")),
                             NamedSharding(mesh, P("d", None))),
            out_shardings=NamedSharding(mesh, P())).lower(x, w).compile()
st = hlo_stats(c.as_text())
assert st.bytes_by_kind.get("all-reduce", 0) > 0 or \
       st.bytes_by_kind.get("reduce-scatter", 0) > 0, st.bytes_by_kind
# contraction sharded 8-ways: per-device flops = total/8
assert abs(st.flops - 2*1024*512*256/8) / (2*1024*512*256/8) < 1e-6, st.flops
print("SUBPROCESS_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=240)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr


def test_filter_mlp_roofline_structure():
    """Analytic filter-kernel bound: fused cuts the query re-stream bf×,
    drops the epilogue passes, and quantization cuts the dominant weight
    stream — all visible in the three-term model."""
    from repro.analysis.roofline import filter_mlp_roofline
    F, Q, m, h, bf = 1024, 128, 128, 128, 8
    per = filter_mlp_roofline(F, Q, m, h, variant="per_filter")
    fus = filter_mlp_roofline(F, Q, m, h, variant="fused", bf=bf)
    # single-chip kernel: no collective term; both memory-bound at this shape
    assert per.link_bytes_per_device == 0 and fus.link_bytes_per_device == 0
    assert per.dominant == "memory" and fus.dominant == "memory"
    # fused strictly cheaper on bytes, despite the group-sum flops overhead
    assert fus.hbm_bytes_per_device < per.hbm_bytes_per_device
    assert fus.flops_per_device > per.flops_per_device
    assert fus.bound_time < per.bound_time
    # exact traffic deltas: bf× query re-stream cut + 3 epilogue passes
    q_delta = (F - F // bf) * Q * m * 4
    epi = 3 * 2 * F * Q * 4
    assert per.hbm_bytes_per_device - fus.hbm_bytes_per_device == \
        q_delta + epi
    # quantization cuts exactly the w1/w2 element stream (biases/stats stay
    # f32; int8 adds two f32 scales per filter); the shared query stream
    # dilutes the whole-kernel ratio below the raw 4x/2x element cut
    f32 = filter_mlp_roofline(F, Q, m, h, variant="fused")
    i8 = filter_mlp_roofline(F, Q, m, h, variant="fused",
                             weight_dtype="int8")
    bf16 = filter_mlp_roofline(F, Q, m, h, variant="fused",
                               weight_dtype="bfloat16")
    n_w = m * h + h
    assert f32.hbm_bytes_per_device - i8.hbm_bytes_per_device == \
        F * (3 * n_w - 2 * 4)
    assert f32.hbm_bytes_per_device - bf16.hbm_bytes_per_device == F * 2 * n_w
    assert f32.hbm_bytes_per_device / i8.hbm_bytes_per_device > 2.5
    assert f32.hbm_bytes_per_device / bf16.hbm_bytes_per_device > 1.5
    with pytest.raises(ValueError):
        filter_mlp_roofline(8, 8, 8, variant="nope")
