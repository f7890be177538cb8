"""Distributed (shard_map) LeaFi search parity suite.

For each backbone this pins, on a 4-device host mesh:

  * the headline padding-leaf bugfix: shards deliberately carry extra
    padding leaf slots (size 0, (−inf, +inf) boxes), whose pre-fix lower
    bound of 0 let phase 1's argmin probe an empty leaf and waste the bsf
    seed — the probed global bsf (read out of the real shard body) must
    stay finite;
  * the tentpole: the fixed-width compact shard strategy
    (``engine.compact_bsf_cascade``) agrees with the masked-scan shard
    body — through a dual-strategy shard_map program that computes the
    pruning inputs once and runs both strategies on them, and through the
    production ``make_distributed_search`` wiring;
  * the overflow (survivors > capacity) → masked-scan fallback path and a
    shard containing only padding leaves, through the same dual body;
  * the accounting satellite: the psum'd ``total_searched`` return equals
    the sum of the per-shard single-device cascade counts — exactly within
    one program, and within a small cross-program slack against an eager
    single-device oracle;
  * the exact-search recall floor.

A note on assertion strength: the *bitwise* compact==scan contract (given
identical inputs, including borderline prune thresholds) is pinned
in-process in tests/test_engine.py, where both forms consume literally the
same arrays through the same per-op programs.  Inside fused XLA programs
that guarantee does not survive: the scan's slab-sliced distances and the
compaction's gathered distances may differ in the last ulp depending on
the surrounding fusion, a trained filter's prediction is ≈ the bsf *by
construction*, and iSAX leaves share quantized lb values — so a
`threshold > bsf` decision sitting within an ulp can legitimately flip
between compiled programs (observed on CPU for both trained and synthetic
filters).  The distributed assertions therefore check structure exactly
(accounting identity, finiteness) and floats/counts to tight tolerance —
real regressions (a probed padding leaf, a lost shard, a broken fallback)
move these by orders of magnitude more than an ulp tie does.

Runs in subprocesses so the placeholder host devices don't leak into the
rest of the suite.
"""
import subprocess
import sys

import pytest

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import build, distributed, engine, filter_training
from repro.core.summaries import znormalize

backbone = "%(backbone)s"
rng = np.random.default_rng(0)
S = rng.standard_normal((3000, 64), dtype=np.float32).cumsum(axis=1)
cfg = build.LeaFiConfig(backbone=backbone, leaf_capacity=64, n_global=120,
                        n_local=24, t_filter_over_t_series=10.0,
                        train=filter_training.TrainConfig(epochs=20))
lfi = build.build_leafi(S, cfg)
Q = znormalize(S[rng.integers(0, len(S), 16)]
               + 0.3 * rng.standard_normal((16, 64)).astype(np.float32))
Qj = jnp.asarray(Q)

mesh = distributed.make_search_mesh(2, 2)
sharded = distributed.shard_leafi(lfi, n_shards=2, quality_target=0.99)

def pad_leaves(sh, extra):
    # deliberately unbalanced shards: every shard gains `extra` padding
    # leaf slots (size 0, (-inf, +inf) boxes) -- the probe-bug trigger
    def pad2(a, cv=0):
        w = [(0, 0), (0, extra)] + [(0, 0)] * (a.ndim - 2)
        return jnp.pad(a, w, constant_values=cv)
    return dataclasses.replace(
        sh, leaf_start=pad2(sh.leaf_start), leaf_size=pad2(sh.leaf_size),
        lb_lo=pad2(sh.lb_lo, -np.inf), lb_hi=pad2(sh.lb_hi, np.inf),
        w1=pad2(sh.w1), b1=pad2(sh.b1), w2=pad2(sh.w2), b2=pad2(sh.b2),
        y_mean=pad2(sh.y_mean), y_std=pad2(sh.y_std, 1.0),
        offsets=pad2(sh.offsets), has_filter=pad2(sh.has_filter, False))

sharded = pad_leaves(sharded, 3)

def synthetic_filters(sh):
    # zero the stacked MLPs and filter-prune a checkerboard of real leaves
    # via a huge bias: d_F is then -inf or ~1e30, so no *filter* decision
    # can sit within an ulp of the bsf (lb ties remain possible — see the
    # module docstring); exercises an aggressive, deterministic filter
    # cascade independent of training noise
    valid = np.asarray(sh.leaf_size) > 0
    prune = valid & ((np.indices(valid.shape).sum(0) %% 2) == 0)
    return dataclasses.replace(
        sh, w1=jnp.zeros_like(sh.w1), b1=jnp.zeros_like(sh.b1),
        w2=jnp.zeros_like(sh.w2),
        b2=jnp.asarray(np.where(prune, np.float32(1e30), 0.0)),
        y_mean=jnp.zeros_like(sh.y_mean), y_std=jnp.ones_like(sh.y_std),
        offsets=jnp.zeros_like(sh.offsets), has_filter=jnp.asarray(prune))

def blank_shard(sh):                # shard 1 becomes all padding leaves
    return dataclasses.replace(
        sh, leaf_size=sh.leaf_size.at[1].set(0),
        lb_lo=sh.lb_lo.at[1].set(-np.inf),
        lb_hi=sh.lb_hi.at[1].set(np.inf),
        has_filter=sh.has_filter.at[1].set(False))

synth = synthetic_filters(sharded)

def idx_args(sh):
    return (sh.series, sh.leaf_start, sh.leaf_size, sh.lb_lo, sh.lb_hi,
            sh.w1, sh.b1, sh.w2, sh.b2, sh.y_mean, sh.y_std,
            sh.offsets, sh.has_filter)

def dual_run(sh, max_survivors=None):
    # one shard_map program computing the pruning inputs once and running
    # BOTH phase-2 strategies on them: the only sound way to assert bitwise
    # scan==compact parity (see module docstring)
    max_leaf = sh.max_leaf
    def body(series, start, size, lo, hi, w1, b1, w2, b2, y_mean, y_std,
             offsets, has_filter, queries, qcoords):
        series, start, size = series[0], start[0], size[0]
        lb, d_F = distributed._shard_pruning_inputs(
            lo[0], hi[0], w1[0], b1[0], w2[0], b2[0], y_mean[0], y_std[0],
            offsets[0], has_filter[0], size, queries, qcoords)
        probe = engine.probe_best_leaf(series, start, size, lb, queries,
                                       max_leaf)
        bsf0 = jax.lax.pmin(probe, "model")
        bsf_s, ns_s = engine.masked_bsf_scan(series, start, size, lb, d_F,
                                             queries, max_leaf, bsf0)
        bsf_c, ns_c = engine.compact_bsf_cascade(
            series, start, size, lb, d_F, queries, max_leaf, bsf0,
            max_survivors=max_survivors)
        return (jax.lax.pmin(bsf_s, "model")[None],
                jax.lax.psum(ns_s, "model")[None],
                jax.lax.pmin(bsf_c, "model")[None],
                jax.lax.psum(ns_c, "model")[None],
                ns_s[None], bsf0[None])
    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("model"),) * 13 + (P(("data",)), P(("data",))),
        out_specs=(P("model", "data"),) * 6, check_vma=False)
    out = jax.jit(smapped)(*idx_args(sh), Qj, sh.query_coords(Qj))
    nn_s, tot_s, nn_c, tot_c, ns_shard, bsf0 = map(np.asarray, out)
    return nn_s[0], tot_s[0], nn_c[0], tot_c[0], ns_shard, bsf0[0]

def oracle(sh):
    # the two-phase exchange, replayed eagerly with the single-device
    # engine pieces (cross-program: compare with tolerance only)
    qc = sh.query_coords(Qj)
    n_sh = sh.leaf_size.shape[0]
    lbs, dFs, probes = [], [], []
    for s in range(n_sh):
        lb, d_F = distributed._shard_pruning_inputs(
            sh.lb_lo[s], sh.lb_hi[s], sh.w1[s], sh.b1[s], sh.w2[s],
            sh.b2[s], sh.y_mean[s], sh.y_std[s], sh.offsets[s],
            sh.has_filter[s], sh.leaf_size[s], Qj, qc)
        lbs.append(lb); dFs.append(d_F)
        probes.append(engine.probe_best_leaf(
            sh.series[s], sh.leaf_start[s], sh.leaf_size[s], lb, Qj,
            sh.max_leaf))
    bsf0 = jnp.stack(probes).min(0)
    bsfs, ns = [], []
    for s in range(n_sh):
        b, n = engine.masked_bsf_scan(
            sh.series[s], sh.leaf_start[s], sh.leaf_size[s], lbs[s],
            dFs[s], Qj, sh.max_leaf, bsf0)
        bsfs.append(b); ns.append(n)
    return (np.asarray(jnp.stack(bsfs).min(0)),
            np.asarray(jnp.stack(ns).sum(0)), np.asarray(bsf0))

def dist_run(sh, **kw):
    run, *_ = distributed.make_distributed_search(mesh, sh, **kw)
    with mesh:
        nn, total = run(Qj)
    return np.asarray(nn), np.asarray(total)

SLACK = 8      # cross-program searched-count slack (ulp-tied prune flips)

# --- dual-body pins: trained, synthetic, blank, overflow -------------------
for name, sh in (("trained", sharded), ("synthetic", synth),
                 ("blank-shard", blank_shard(synth))):
    ref_nn, ref_tot, _ = oracle(sh)
    for cap in (None, 1):          # default capacity; capacity-1 = overflow
        nn_s, tot_s, nn_c, tot_c, ns_shard, bsf0 = dual_run(
            sh, max_survivors=cap)
        tag = (name, cap)
        # headline regression: the probed global bsf is finite even though
        # every shard carries padding leaves (pre-fix: +inf on such shards)
        assert np.isfinite(bsf0).all(), (tag, bsf0)
        # accounting: psum total == sum of per-shard cascade counts, exact
        np.testing.assert_array_equal(tot_s, ns_shard.sum(0),
                                      err_msg=str(tag))
        assert np.isfinite(nn_s).all(), tag
        # tentpole: compact agrees with the masked-scan body (shared
        # pruning inputs; tolerance per the module docstring)
        np.testing.assert_allclose(nn_c, nn_s, rtol=2e-6, err_msg=str(tag))
        assert np.abs(tot_c.astype(int)
                      - tot_s.astype(int)).max() <= SLACK, (tag, tot_c,
                                                            tot_s)
        # cross-program: the eager single-device oracle agrees
        np.testing.assert_allclose(nn_s, ref_nn, rtol=2e-6, err_msg=str(tag))
        assert np.abs(tot_s.astype(int)
                      - ref_tot.astype(int)).max() <= SLACK, (tag, tot_s,
                                                              ref_tot)

ref_nn, ref_tot, _ = oracle(sharded)

# production wiring: make_distributed_search (both strategies) vs oracle
nn_by = {}
for strategy in ("scan", "compact"):
    nn, tot = dist_run(sharded, strategy=strategy)
    np.testing.assert_allclose(nn, ref_nn, rtol=2e-6, err_msg=strategy)
    assert np.abs(tot.astype(int) - ref_tot.astype(int)).max() <= SLACK
    nn_by[strategy] = nn
np.testing.assert_allclose(nn_by["compact"], nn_by["scan"], rtol=2e-6)

# exactness floor: recall vs exact single-device search
ref_exact = lfi.search_exact(Q)
nn_c = nn_by["compact"]
recall = (nn_c <= ref_exact.dists[:, 0] * (1 + 1e-5) + 1e-6).mean()
assert recall >= 0.9, recall
assert (nn_c >= ref_exact.dists[:, 0] - 1e-4).all()

print("DIST_OK", backbone, "recall", recall)
"""


@pytest.mark.parametrize("backbone", ["dstree", "isax"])
def test_distributed_search_matches(backbone):
    code = CODE % {"backbone": backbone}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900)
    assert f"DIST_OK {backbone}" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]


PQ_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.core import build, conformal, distributed, filter_training, search
from repro.core.summaries import znormalize

backbone = "%(backbone)s"
rng = np.random.default_rng(0)
S = rng.standard_normal((3000, 64), dtype=np.float32).cumsum(axis=1)
cfg = build.LeaFiConfig(backbone=backbone, leaf_capacity=64, n_global=120,
                        n_local=24, t_filter_over_t_series=10.0,
                        train=filter_training.TrainConfig(epochs=20))
lfi = build.build_leafi(S, cfg)
Q = znormalize(S[rng.integers(0, len(S), 16)]
               + 0.3 * rng.standard_normal((16, 64)).astype(np.float32))
Qj = jnp.asarray(Q)
L = lfi.index.n_leaves
TARGETS = np.asarray([0.9, 0.95, 0.99])
targets = TARGETS[rng.integers(0, 3, 16)]            # mixed micro-batch

mesh = distributed.make_search_mesh(2, 2)
sharded = distributed.shard_leafi(lfi, n_shards=2, quality_target=0.99)
assert sharded.leaf_global is not None
lg = np.asarray(sharded.leaf_global)
real = np.asarray(sharded.leaf_size) > 0
# the slot->global map covers every leaf exactly once; padding slots carry L
assert sorted(lg[real].tolist()) == list(range(L))
assert (lg[~real] == L).all()

run, *_ = distributed.make_distributed_search(
    mesh, sharded, per_query_offsets=True)
qoff = conformal.scatter_offsets(lfi.tuner, lfi.leaf_ids, L, targets)
inf_ub = np.full(16, np.inf, np.float32)
with mesh:
    nn, tot = run(Qj, jnp.asarray(qoff), jnp.asarray(inf_ub))
nn, tot = np.asarray(nn), np.asarray(tot)

# parity vs the single-device per-query-offset search, pinned per target
# group (cross-program: tolerance, cf. the module docstring)
ref = search.search_batched(lfi.index, Q, k=1, quality_target=targets,
                            filter_params=lfi.filter_params,
                            leaf_ids=lfi.leaf_ids, tuner=lfi.tuner)
for t in TARGETS:
    sel = targets == t
    if sel.any():
        np.testing.assert_allclose(nn[sel], ref.dists[sel, 0], rtol=2e-6,
                                   err_msg=str(t))

# homogeneous rows == the baked single-offset program (same target)
run1, *_ = distributed.make_distributed_search(mesh, sharded)
qoff99 = conformal.scatter_offsets(lfi.tuner, lfi.leaf_ids, L,
                                   np.full(16, 0.99))
with mesh:
    nn_pq, _ = run(Qj, jnp.asarray(qoff99), jnp.asarray(inf_ub))
    nn_1, _ = run1(Qj)
np.testing.assert_allclose(np.asarray(nn_pq), np.asarray(nn_1), rtol=2e-6)

# +inf offset rows disable every filter: exact answers from the same program
inf_rows = jnp.full((16, L), np.inf, jnp.float32)
with mesh:
    nn_ex, tot_ex = run(Qj, inf_rows, jnp.asarray(inf_ub))
exact = lfi.search_exact(Q)
np.testing.assert_allclose(np.asarray(nn_ex), exact.dists[:, 0], rtol=2e-6)

# a valid prune-only warm bound on the exact path (where its bitwise
# contract holds: it only tightens the lb test) never changes the answer
# and never scans more leaves
ub = (exact.dists[:, 0] * (1 + 1e-6) + 1e-6).astype(np.float32)
with mesh:
    nn_w, tot_w = run(Qj, inf_rows, jnp.asarray(ub))
np.testing.assert_allclose(np.asarray(nn_w), np.asarray(nn_ex), rtol=2e-6)
assert np.asarray(tot_w).sum() <= np.asarray(tot_ex).sum()

print("PQ_OK", backbone)
"""


@pytest.mark.parametrize("backbone", ["dstree", "isax"])
def test_distributed_per_query_offsets(backbone):
    code = PQ_CODE % {"backbone": backbone}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900)
    assert f"PQ_OK {backbone}" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]


SERVE_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import numpy as np, jax
from repro.core import build, distributed, filter_training
from repro.core.summaries import znormalize
from repro.serving import (DistributedExecutor, MicroBatcher,
                           ServingSession, poisson_trace)

rng = np.random.default_rng(0)
S = rng.standard_normal((3000, 64), dtype=np.float32).cumsum(axis=1)
cfg = build.LeaFiConfig(backbone="dstree", leaf_capacity=64, n_global=120,
                        n_local=24, t_filter_over_t_series=10.0,
                        train=filter_training.TrainConfig(epochs=20))
lfi = build.build_leafi(S, cfg)
pool = znormalize(S[rng.integers(0, len(S), 32)]
                  + 0.3 * rng.standard_normal((32, 64)).astype(np.float32))
trace = poisson_trace(pool, rate=800.0, n_requests=48,
                      targets=(0.9, 0.99), ks=(1,), seed=3)
svc = lambda b: 1e-3 * max(b.bucket / 8, 0.25)

mesh = distributed.make_search_mesh(1, 2)            # 1x2 host mesh

def serve(pipeline):
    ex = DistributedExecutor(lfi, mesh)
    s = ServingSession(lfi, warm_start=True, executor=ex)
    with mesh:
        s.warmup(max_batch=8, ks=(1,), queries=pool)
        return s.serve(trace,
                       batcher=MicroBatcher(max_batch=8, max_wait=0.004),
                       service_time=svc, pipeline=pipeline)

r0 = serve(0)
r1 = serve(1)
host = ("wall", "dispatch_s", "harvest_s", "t_disp", "t_done")
strip = lambda log: [{k: v for k, v in b.items() if k not in host}
                     for b in log]
assert strip(r0["batches"]) == strip(r1["batches"])
for rid in r0["completions"]:
    assert r0["completions"][rid]["result"] == \
        r1["completions"][rid]["result"], rid        # bitwise

# the shard_map answers match the single-host session on the same trace
single = ServingSession(lfi)
single.warmup(max_batch=8, ks=(1,), queries=pool)
rs = single.serve(trace, batcher=MicroBatcher(max_batch=8, max_wait=0.004),
                  service_time=svc)
for rid in rs["completions"]:
    a = rs["completions"][rid]["result"]["dist"]
    b = r0["completions"][rid]["result"]["dist"]
    assert abs(a - b) <= 2e-5 * max(abs(a), 1.0), (rid, a, b)

print("DIST_SERVE_OK")
"""


def test_distributed_serving_pipelined_parity_on_host_mesh():
    """1×2 host mesh: the DistributedExecutor session serves the identical
    trace bitwise under serial and pipelined dispatch, and its answers match
    the single-host session to float tolerance."""
    r = subprocess.run([sys.executable, "-c", SERVE_CODE],
                       capture_output=True, text=True, timeout=900)
    assert "DIST_SERVE_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]


TRACE_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.core import build, distributed, filter_training
from repro.core.summaries import znormalize

rng = np.random.default_rng(0)
S = rng.standard_normal((3000, 64), dtype=np.float32).cumsum(axis=1)
cfg = build.LeaFiConfig(backbone="dstree", leaf_capacity=64, n_global=120,
                        n_local=24, t_filter_over_t_series=10.0,
                        train=filter_training.TrainConfig(epochs=20))
lfi = build.build_leafi(S, cfg)
Q = znormalize(S[rng.integers(0, len(S), 16)]
               + 0.3 * rng.standard_normal((16, 64)).astype(np.float32))
Qj = jnp.asarray(Q)

mesh = distributed.make_search_mesh(2, 2)
sharded = distributed.shard_leafi(lfi, n_shards=2, quality_target=0.99)
n_shards, P_slots = sharded.leaf_size.shape

for strategy in ("scan", "compact"):
    run0, *_ = distributed.make_distributed_search(mesh, sharded,
                                                   strategy=strategy)
    runt, *_ = distributed.make_distributed_search(mesh, sharded,
                                                   strategy=strategy,
                                                   trace=True)
    with mesh:
        nn0, tot0 = run0(Qj)
        nn1, tot1, tr = runt(Qj)
    # trace=True must not perturb the exchange (same programs modulo the
    # psum'd int32 side outputs)
    np.testing.assert_array_equal(np.asarray(nn0), np.asarray(nn1),
                                  err_msg=strategy)
    np.testing.assert_array_equal(np.asarray(tot0), np.asarray(tot1),
                                  err_msg=strategy)
    # global accounting identity (see distributed._make_shard_body): each
    # shard probes one leaf that stays cascade-accounted, so probed == S
    # and the pruned counts partition the S*P slot grid minus survivors
    pruned = (np.asarray(tr.pruned_box) + np.asarray(tr.pruned_seed)
              + np.asarray(tr.pruned_filter))
    np.testing.assert_array_equal(
        pruned, n_shards * P_slots - np.asarray(tr.survivors),
        err_msg=strategy)
    np.testing.assert_array_equal(np.asarray(tr.probed),
                                  np.full(16, n_shards), err_msg=strategy)
    assert (np.asarray(tr.distances) > 0).all(), strategy

print("TRACE_OK")
"""


def test_distributed_trace_parity_and_global_accounting():
    """2-shard host mesh: the traced shard body returns bitwise-identical
    nn/searched outputs and a psum'd CascadeTrace whose counts satisfy the
    global identity (sum pruned == S*P - survivors, probed == S)."""
    r = subprocess.run([sys.executable, "-c", TRACE_CODE],
                       capture_output=True, text=True, timeout=900)
    assert "TRACE_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-4000:]


AUDIT_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.core import build, distributed, filter_training
from repro.core.summaries import znormalize
from repro.obs import audit as obs_audit

rng = np.random.default_rng(0)
S = rng.standard_normal((3000, 64), dtype=np.float32).cumsum(axis=1)
cfg = build.LeaFiConfig(backbone="dstree", leaf_capacity=64, n_global=120,
                        n_local=24, t_filter_over_t_series=10.0,
                        train=filter_training.TrainConfig(epochs=20))
lfi = build.build_leafi(S, cfg)
Q = znormalize(S[rng.integers(0, len(S), 16)]
               + 0.3 * rng.standard_normal((16, 64)).astype(np.float32))
Qj = jnp.asarray(Q)
L = lfi.index.n_leaves

mesh = distributed.make_search_mesh(2, 2)
sharded = distributed.shard_leafi(lfi, n_shards=2, quality_target=0.99)
n_shards, P_slots = sharded.leaf_size.shape
SLACK = 8      # cross-program searched-count slack (ulp-tied prune flips)

for strategy in ("scan", "compact"):
    run0, *_ = distributed.make_distributed_search(mesh, sharded,
                                                   strategy=strategy)
    runa, *_ = distributed.make_distributed_search(mesh, sharded,
                                                   strategy=strategy,
                                                   audit=True)
    with mesh:
        nn0, tot0 = run0(Qj)
        nn1, tot1, fa = runa(Qj)
    # the audited program's answers are bitwise; the searched count may
    # sit an ulp-tie away across differently-fused programs (cf. the
    # module docstring's assertion-strength note)
    np.testing.assert_array_equal(np.asarray(nn0), np.asarray(nn1),
                                  err_msg=strategy)
    assert np.abs(np.asarray(tot1).astype(int)
                  - np.asarray(tot0).astype(int)).max() <= SLACK, strategy
    fa_np = jax.tree.map(np.asarray, fa)
    assert fa_np.kept.shape == (n_shards, P_slots), strategy
    assert fa_np.resid_buckets.shape == (n_shards, P_slots,
                                         obs_audit.N_BUCKETS), strategy
    # per-shard-slot accounting identity, exact: after the data-axis psum
    # every (shard, slot) has partitioned the full 16-query batch
    resid = np.asarray(obs_audit.accounting_residual_leaf(fa, 16))
    assert not resid.any(), (strategy, resid)
    # padding slots never enter a distance pass
    pad = np.asarray(sharded.leaf_size) == 0
    assert not fa_np.kept[pad].any(), strategy
    assert not fa_np.scored[pad].any(), strategy
    # fold to global leaf order: identity again, scratch row absorbed
    g = obs_audit.scatter_global(fa, sharded.leaf_global, L)
    g_np = jax.tree.map(np.asarray, g)
    assert g_np.kept.shape == (L,), strategy
    assert not np.asarray(
        obs_audit.accounting_residual_leaf(g, 16)).any(), strategy
    # residual bookkeeping survives the collectives + the fold
    np.testing.assert_array_equal(g_np.resid_buckets.sum(-1),
                                  g_np.resid_count, err_msg=strategy)
    assert (g_np.violations <= g_np.resid_count).all(), strategy
    assert (g_np.resid_count <= g_np.scored).all(), strategy
    assert g_np.kept.sum() > 0, strategy
    assert (g_np.pruned_box + g_np.pruned_seed
            + g_np.pruned_filter).sum() > 0, strategy

print("AUDIT_OK")
"""


def test_distributed_audit_accounting_and_parity():
    """2-shard host mesh: the audited shard body answers bitwise, its
    per-(shard, slot) FilterAudit satisfies the accounting identity exactly
    after the data-axis psum, and the scatter_global fold to leaf order
    preserves both the identity and the residual bookkeeping."""
    r = subprocess.run([sys.executable, "-c", AUDIT_CODE],
                       capture_output=True, text=True, timeout=900)
    assert "AUDIT_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-4000:]
