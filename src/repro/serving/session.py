"""Serving session: a warmed, checkpointable facade over a built LeaFi index.

A :class:`ServingSession` owns the three things a long-lived serving process
needs beyond the engine itself:

* **cold start** — the built index (backbone arrays, stacked filter params,
  conformal tuner) round-trips through :mod:`repro.checkpoint` as one atomic
  pytree checkpoint (:func:`save_index` / :func:`load_index`), so a restart
  loads in seconds instead of re-running Alg. 1's build pipeline;
* **program cache pre-warm** — :meth:`ServingSession.warmup` drives one
  dummy search per (bucket, k) shape through the session's engine strategy,
  so jit compilation happens before traffic, not under it (the batcher's
  pow2 buckets are what keeps this set small);
* **execution + accounting** — :meth:`ServingSession.execute` answers one
  :class:`~repro.serving.batcher.MicroBatch` (per-query quality targets
  lowered to (B, F) conformal offset rows), and :meth:`ServingSession.serve`
  drives a whole open-loop trace through the micro-batcher, folding latency,
  pruning, survivor and recall counters into the session's
  :class:`~repro.serving.telemetry.Telemetry`.

Execution is split into an async **dispatch** (submit the batch's engine
programs; JAX returns device-array futures) and a blocking **harvest**
(materialize results), so :meth:`ServingSession.serve` can run *pipelined*
(``pipeline=1``): batch N+1's host-side formation and dispatch overlap
batch N's device execution.  Cross-batch **bsf warm-starting**
(``warm_start=True``) seeds each batch with prune-only upper bounds derived
from recently answered queries (:mod:`repro.serving.warmstart`), and
:class:`DistributedExecutor` routes the same micro-batches through the
shard_map'd multi-chip search with per-query conformal offset rows.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from .. import checkpoint
from ..core import build, conformal, search
from ..core.flat_index import FlatIndex
from ..obs import span
from . import batcher as batcher_mod
from .batcher import MicroBatch, MicroBatcher, Request, _pow2_floor
from .telemetry import (Telemetry, latency_percentiles,
                        observe_recall_cell, recall_summary)
from .warmstart import BsfCache

# ---------------------------------------------------------------------------
# index persistence (cold start)
# ---------------------------------------------------------------------------

_CONFIG_FIELDS = ("backbone", "leaf_capacity", "n_segments", "word_len",
                  "n_global", "n_local", "calib_fraction", "a",
                  "t_filter_over_t_series", "filter_memory_budget_bytes",
                  "hidden", "filter_type", "weight_dtype", "seed")


def save_index(path: str, lfi: build.LeaFiIndex,
               metadata: Optional[dict] = None) -> None:
    """Checkpoint a built LeaFi index (atomic; see checkpoint.save_pytree).

    Arrays (series, leaf layout, summarization payload, stacked filter
    params, tuner knots) go into the pytree; scalars and structure (kind,
    sizes, config) ride in the metadata blob, so :func:`load_index` can
    reconstruct without a template object.
    """
    idx = lfi.index
    tuner = lfi.tuner
    if lfi.filter_params is not None and \
            str(lfi.filter_params["w1"].dtype) == "bfloat16":
        # np.savez silently drops the bfloat16 dtype (round-trips as raw
        # void bytes), so bf16 indexes don't checkpoint: save the float32
        # index and build.requantize_leafi after load instead.
        raise ValueError(
            "bfloat16 filter weights cannot be checkpointed (np.savez "
            "loses the dtype); save the float32 index and requantize "
            "after load (build.requantize_leafi)")
    calib = getattr(lfi, "calib", None)
    tree = {
        "series": np.asarray(idx.series),
        "order": np.asarray(idx.order),
        "leaf_start": np.asarray(idx.leaf_start),
        "leaf_size": np.asarray(idx.leaf_size),
        "payload": {k: np.asarray(v) for k, v in idx.payload.items()},
        "filter_params": ({k: np.asarray(v)
                           for k, v in lfi.filter_params.items()}
                          if lfi.filter_params is not None else {}),
        "leaf_ids": np.asarray(lfi.leaf_ids, np.int64),
        "tuner": ({"knots_q": tuner.knots_q, "knots_o": tuner.knots_o,
                   "slopes": tuner.slopes, "max_offset": tuner.max_offset}
                  if tuner is not None else {}),
        "calib": ({"queries": np.asarray(calib.queries),
                   "d_lb": np.asarray(calib.d_lb),
                   "d_L": np.asarray(calib.d_L)}
                  if calib is not None else {}),
    }
    cfg = dataclasses.asdict(lfi.config)
    cfg.pop("train", None)                    # training recipe: not needed
    meta = {"kind": idx.kind, "max_leaf_size": int(idx.max_leaf_size),
            "n_series": int(idx.n_series), "length": int(idx.length),
            "config": cfg,
            "build_report": {k: float(v)
                             for k, v in lfi.build_report.items()}}
    meta.update(metadata or {})
    checkpoint.save_pytree(path, tree, meta)


def load_index(path: str) -> build.LeaFiIndex:
    """Rebuild a LeaFiIndex from a :func:`save_index` checkpoint.

    Search over the loaded index is pinned identical to the saved one
    (tests/test_serving.py): the arrays round-trip verbatim and the engine
    sees the same inputs in the same process context.  The backbone arrays
    and filter parameters are placed on the default device here, once.
    """
    flat, meta = checkpoint.load_pytree(path)

    def group(name: str):
        """One top-level entry: a leaf array, or a dict of its children."""
        pre = f"['{name}']"
        if pre in flat:
            return flat[pre]
        return {k[len(pre) + 1:][2:-2]: v
                for k, v in flat.items() if k.startswith(pre + "/")}

    index = FlatIndex(
        kind=meta["kind"], series=group("series"), order=group("order"),
        leaf_start=group("leaf_start"), leaf_size=group("leaf_size"),
        max_leaf_size=int(meta["max_leaf_size"]),
        n_series=int(meta["n_series"]), length=int(meta["length"]),
        payload=group("payload")).on_device()
    params = {k: jax.device_put(v)
              for k, v in group("filter_params").items()} or None
    tn = group("tuner")
    tuner = conformal.AutoTuner(**tn) if tn else None
    cal = group("calib")
    calib = build.CalibSplit(**cal) if cal else None
    cfg_kw = {k: meta["config"][k] for k in _CONFIG_FIELDS
              if k in meta.get("config", {})}
    return build.LeaFiIndex(
        index=index, filter_params=params, leaf_ids=group("leaf_ids"),
        tuner=tuner, config=build.LeaFiConfig(**cfg_kw),
        build_report=dict(meta.get("build_report", {})), calib=calib)


# ---------------------------------------------------------------------------
# the session
# ---------------------------------------------------------------------------


def _pow2_buckets(max_batch: int) -> List[int]:
    """Every bucket a MicroBatcher capped at ``max_batch`` can emit."""
    return [1 << i for i in range(_pow2_floor(max_batch).bit_length())]


# ---------------------------------------------------------------------------
# distributed execution backend (socket → shard_map)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _DistResult:
    """SearchResult-shaped view of the distributed exchange's outputs.

    The multi-chip search reduces a single nn distance and a psum'd
    searched-leaf total per query; per-leaf prune attribution and series
    ids stay shard-local (they never cross the pmin), so those fields are
    absent here.
    """
    dists: np.ndarray            # (Q, 1)
    searched: np.ndarray         # (Q,)
    n_leaves: int
    computed: Optional[np.ndarray] = None
    ids: Optional[np.ndarray] = None


@dataclasses.dataclass
class _PendingDist:
    """In-flight distributed batch: device-array futures until result()."""
    nn: object
    n_searched: object
    n_leaves: int

    def block_until_ready(self) -> "_PendingDist":
        jax.block_until_ready(self.nn)
        return self

    def result(self) -> _DistResult:
        """Blocks on the device; spans as ``search.PendingSearch.result``:
        ``search.wait``, then ``search.fetch``."""
        out = [self.nn, self.n_searched]
        with span("search.wait", cat="search", q=int(self.nn.shape[0]),
                  k=1):
            jax.block_until_ready(out)
        with span("search.fetch", cat="search", n_arrays=len(out),
                  bytes=int(sum(x.nbytes for x in out))):
            return _DistResult(dists=np.asarray(self.nn)[:, None],
                               searched=np.asarray(self.n_searched),
                               n_leaves=self.n_leaves)


class DistributedExecutor:
    """Routes serving micro-batches through the shard_map multi-chip search.

    Builds one jitted per-query-offset program over ``mesh``
    (:func:`repro.core.distributed.make_distributed_search` with
    ``per_query_offsets=True``): each query carries its own (L,) conformal
    offset row — mixed quality targets in one compiled program — plus the
    (Q,) prune-only ``bsf_ub`` warm bound.  ``donate=True`` hands the
    per-call query/offset/bound buffers to XLA so steady-state serving
    re-uses their device allocations (skipped on CPU, where donation is
    ignored).  k=1 only: the distributed exchange reduces a single nn
    distance per query.
    """

    def __init__(self, lfi: build.LeaFiIndex, mesh, *,
                 data_axes=("data",), model_axis: str = "model",
                 strategy: str = "compact",
                 max_survivors: Optional[int] = None,
                 dist_impl: Optional[str] = None, donate: bool = True):
        from ..core import distributed
        self.lfi = lfi
        self.n_leaves = lfi.index.n_leaves
        n_model = int(mesh.shape[model_axis])
        self.sharded = distributed.shard_leafi(lfi, n_model, mesh=mesh,
                                               model_axis=model_axis)
        self.run, self._idx_args, _, _ = distributed.make_distributed_search(
            mesh, self.sharded, data_axes=data_axes, model_axis=model_axis,
            strategy=strategy, max_survivors=max_survivors,
            dist_impl=dist_impl, per_query_offsets=True, donate=donate)

    def _offset_rows(self, targets, B: int) -> np.ndarray:
        """Per-query (B, L) conformal offset rows; +inf rows ⇒ exact search.

        ``d_F = pred − offset``, so a +inf offset drives every filter bound
        to −inf — the filter cascade can never fire and the distributed
        search answers exactly, from the same compiled program.
        """
        L = self.n_leaves
        if targets is None:
            return np.full((B, L), np.inf, np.float32)
        if self.lfi.tuner is None:
            return np.zeros((B, L), np.float32)
        off = conformal.scatter_offsets(
            self.lfi.tuner, self.lfi.leaf_ids, L,
            np.asarray(targets, np.float64))
        return np.asarray(off, np.float32).reshape(B, L)

    def dispatch(self, queries: np.ndarray, targets, k: int,
                 bsf_ub: Optional[np.ndarray] = None) -> _PendingDist:
        if int(k) != 1:
            raise ValueError("DistributedExecutor serves k=1 only "
                             f"(got k={k})")
        q = np.asarray(queries, np.float32)
        ub = (np.full(q.shape[0], np.inf, np.float32) if bsf_ub is None
              else np.asarray(bsf_ub, np.float32))
        nn, n_s = self.run(q, self._offset_rows(targets, q.shape[0]), ub)
        return _PendingDist(nn=nn, n_searched=n_s, n_leaves=self.n_leaves)


@dataclasses.dataclass
class PendingBatch:
    """One dispatched micro-batch awaiting harvest (FIFO, seq-ordered)."""
    pending: object               # PendingSearch | _PendingDist
    batch: MicroBatch
    seq: int
    # warm-start seed the batch was dispatched with (None when cold/off);
    # kept so the shadow sampler can attribute seed-bound exclusions
    bsf_ub: Optional[np.ndarray] = None


class ServingSession:
    """A query-serving runtime over one built LeaFi index.

    ``warm_start=True`` enables cross-batch bsf warm-starting: each
    dispatched batch is seeded with prune-only upper bounds from a rolling
    cache of recently answered queries (see :mod:`repro.serving.warmstart`
    for the triangle-inequality bound and the exactness argument).  Harvested
    results are *staged* and only committed to the cache ``warm_lag`` batches
    later, which makes serial and pipelined serving (any
    ``pipeline <= warm_lag + 1``) observe identical cache states — the
    trace-replay determinism tests pin serial vs ``pipeline=1`` bitwise.

    ``executor`` swaps the single-host engine for a
    :class:`DistributedExecutor` (k=1): batches flow through the shard_map
    search with per-query conformal offset rows instead of
    ``search_batched``.

    ``audit=True`` threads the engine's per-leaf
    :class:`~repro.obs.audit.FilterAudit` through every served batch
    (results stay bitwise identical) and folds it into the telemetry's
    :class:`~repro.obs.health.LeafHealthBoard`; ``shadow_rate > 0``
    attaches a :class:`~repro.serving.shadow.ShadowSampler` that captures
    a deterministic fraction of requests at harvest for off-critical-path
    exact-scan auditing (``serve`` drains it once per trace).  Both are
    single-host features: the distributed executor's exchange reduces a
    single nn distance, so there is nothing leaf-wise to audit host-side.
    """

    def __init__(self, lfi: build.LeaFiIndex, *, strategy: str = "compact",
                 dist_impl: Optional[str] = None,
                 telemetry: Optional[Telemetry] = None,
                 warm_start: bool = False, warm_lag: int = 1,
                 warm_capacity: int = 256,
                 executor: Optional[DistributedExecutor] = None,
                 audit: bool = False, shadow_rate: float = 0.0,
                 shadow_seed: int = 0):
        self.lfi = lfi
        self.strategy = strategy
        self.dist_impl = dist_impl
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self.warm_start = bool(warm_start)
        self.warm_lag = int(warm_lag)
        self.warm_cache = BsfCache(capacity=warm_capacity)
        self.executor = executor
        self.audit = bool(audit) and executor is None
        self.shadow: Optional["ShadowSampler"] = None
        if shadow_rate > 0.0:
            from .shadow import ShadowSampler
            self.shadow = ShadowSampler(self, rate=shadow_rate,
                                        seed=shadow_seed)
        self._seq = 0
        self._warmed: set = set()

    # -- cold start ---------------------------------------------------------

    @classmethod
    def from_checkpoint(cls, path: str, **kw) -> "ServingSession":
        return cls(load_index(path), **kw)

    def save(self, path: str, metadata: Optional[dict] = None) -> None:
        save_index(path, self.lfi, metadata)

    # -- program pre-warm ---------------------------------------------------

    def warmup(self, *, max_batch: int = 64, ks: Sequence[int] = (1,),
               buckets: Optional[Sequence[int]] = None,
               queries: Optional[np.ndarray] = None,
               targets: Sequence[float] = (0.9, 0.99)) -> int:
        """Compile the per-(bucket, k) programs before traffic arrives.

        ``queries`` should be representative of live traffic when possible —
        the compact strategy's inner programs are additionally keyed on
        survivor-count buckets, which depend on how well real queries prune
        (the scan strategy is exactly one program per (bucket, k)).  Returns
        the number of (bucket, k) shapes warmed.
        """
        buckets = list(buckets) if buckets is not None \
            else _pow2_buckets(max_batch)
        if queries is None:
            idx = self.lfi.index
            queries = np.asarray(idx.series[:max(buckets)])
        n = 0
        for k in ks:
            for b in buckets:
                if (b, k) in self._warmed:
                    continue
                q = np.asarray(queries)[np.arange(b) % len(queries)]
                t = np.asarray(targets, np.float64)[np.arange(b)
                                                    % len(targets)]
                self._search_async(q, t, k).result()
                self._warmed.add((b, k))
                n += 1
        return n

    # -- execution ----------------------------------------------------------

    def _search_async(self, queries: np.ndarray, targets, k: int,
                      bsf_ub: Optional[np.ndarray] = None):
        """Dispatch one batch through the session's execution backend.

        Returns a pending handle (``.result()`` blocks): the distributed
        executor when one is attached, else the single-host async engine
        path with per-query targets lowered to (B, F) offset rows.
        """
        if self.executor is not None:
            return self.executor.dispatch(queries, targets, k, bsf_ub)
        lfi = self.lfi
        return search.search_batched_async(
            lfi.index, queries, k=k, filter_params=lfi.filter_params,
            leaf_ids=lfi.leaf_ids, tuner=lfi.tuner,
            quality_target=targets, use_filters=targets is not None,
            strategy=self.strategy, dist_impl=self.dist_impl,
            filter_type=getattr(lfi.config, "filter_type", "mlp"),
            bsf_ub=bsf_ub, audit=self.audit)

    def search(self, queries: np.ndarray,
               quality_targets=None, k: int = 1,
               record: bool = True, **kw) -> search.SearchResult:
        """One batched search; per-query targets lowered to offset rows."""
        lfi = self.lfi
        kw.setdefault("filter_type", getattr(lfi.config, "filter_type",
                                             "mlp"))
        res = search.search_batched(
            lfi.index, queries, k=k, filter_params=lfi.filter_params,
            leaf_ids=lfi.leaf_ids, tuner=lfi.tuner,
            quality_target=quality_targets,
            use_filters=quality_targets is not None,
            strategy=self.strategy, dist_impl=self.dist_impl, **kw)
        if record:
            Q = np.atleast_2d(queries).shape[0]
            self.telemetry.record_batch(res, n_valid=Q, bucket=Q)
        return res

    def search_exact(self, queries: np.ndarray,
                     k: int = 1) -> search.SearchResult:
        return self.search(queries, quality_targets=None, k=k, record=False)

    def dispatch(self, batch: MicroBatch) -> PendingBatch:
        """Submit one micro-batch asynchronously (returns before compute).

        Order of operations matters for determinism: the warm cache first
        *commits* staged results from batches ``<= seq − 1 − warm_lag``
        (identical in serial and pipelined serving — see the class
        docstring), then seeds this batch's prune-only bounds.  Host-side
        cost (offset lowering + program submit), the duration of the
        ``serve.dispatch`` span, is recorded as the ``form`` latency
        phase; per-request queue waits (arrival → batch formation, virtual
        clock) ride along.
        """
        seq = self._seq
        self._seq += 1
        with span("serve.dispatch", cat="serve", seq=seq,
                  bucket=batch.bucket, n_valid=batch.n_valid,
                  k=batch.k) as sp:
            bsf_ub = None
            if self.warm_start:
                self.warm_cache.commit_through(seq - 1 - self.warm_lag)
                bsf_ub = self.warm_cache.seed(batch.queries, batch.k)
            pending = self._search_async(batch.queries, batch.targets,
                                         batch.k, bsf_ub=bsf_ub)
        self.telemetry.record_phases(
            queue_wait=(batch.formed_at - batch.arrivals).tolist(),
            form_s=sp.dur)
        return PendingBatch(pending=pending, batch=batch, seq=seq,
                            bsf_ub=bsf_ub)

    def harvest(self, pb: PendingBatch):
        """Block on one dispatched batch; fold telemetry + warm staging.

        The ``serve.harvest`` span's duration is the ``exec`` latency
        phase."""
        with span("serve.harvest", cat="serve", seq=pb.seq,
                  bucket=pb.batch.bucket, n_valid=pb.batch.n_valid) as sp:
            res = pb.pending.result()
        self.telemetry.record_phases(exec_s=sp.dur)
        b = pb.batch
        if self.warm_start:
            kth = np.asarray(res.dists)[:b.n_valid, -1]
            self.warm_cache.stage(pb.seq, b.queries[:b.n_valid], kth, b.k)
        self.telemetry.record_batch(res, n_valid=b.n_valid, bucket=b.bucket)
        if getattr(res, "audit", None) is not None:
            # audit planes cover every bucket slot (padded rows repeat row
            # 0 — real queries for the accounting identity's purposes)
            self.telemetry.record_audit(res.audit, n_queries=b.bucket)
        if self.shadow is not None:
            self.shadow.capture(b, res, bsf_ub=pb.bsf_ub)
        return res

    def execute(self, batch: MicroBatch):
        """Answer one micro-batch synchronously (dispatch + harvest)."""
        return self.harvest(self.dispatch(batch))

    # -- open-loop serving --------------------------------------------------

    def serve(self, trace: Sequence[Request], *,
              batcher: Optional[MicroBatcher] = None,
              recall_oracle: Optional[Dict[int, float]] = None,
              service_time: Optional[Callable[[MicroBatch], float]] = None,
              pipeline: int = 0) -> dict:
        """Drive a whole arrival trace; returns a *per-trace* report.

        Every number in the report describes this trace alone — the
        session's :attr:`telemetry` keeps the rolling lifetime view across
        traces (and is also fed by this run).  Completions store a
        per-request projection (top-1 distance + searched count), not the
        batch results, so memory stays O(1) per request on long traces.

        ``recall_oracle`` maps rid → exact 1-NN distance; when given, each
        completion is scored against it (the paper's recall@1 rule) and
        folded into the per-target-group recall estimators.
        ``service_time`` replaces measured wall-clock with injected
        per-batch costs (fully deterministic runs for tests; see
        benchmarks/serve_bench.py for the fixed-schedule-replay use).

        ``pipeline=N`` (N ≥ 1) serves through
        :func:`~repro.serving.batcher.run_trace_pipelined` with up to N
        batches in flight — dispatch of batch N+1 overlaps device execution
        of batch N.  Requires an injected ``service_time`` (the virtual
        clock cannot be measured while execution overlaps); the batch
        sequence, completion times, and results are identical to the serial
        loop on the same trace (tests pin this bitwise).
        """
        batcher = batcher or MicroBatcher()

        def extract(res: search.SearchResult, pos: int) -> dict:
            return {"dist": float(np.asarray(res.dists)[pos, 0]),
                    "searched": float(np.asarray(res.searched)[pos]),
                    "n_leaves": res.n_leaves}

        if pipeline:
            completions, batch_log = batcher_mod.run_trace_pipelined(
                trace, batcher, self.dispatch, self.harvest,
                service_time=service_time, extract=extract,
                max_in_flight=pipeline)
        else:
            completions, batch_log = batcher_mod.run_trace(
                trace, batcher, self.execute, service_time=service_time,
                extract=extract)
        lats: List[float] = []
        searched: List[float] = []
        for c in completions.values():
            self.telemetry.record_latency(c["latency"])
            lats.append(c["latency"])
            searched.append(c["result"]["searched"])
        # score recall with the calibration-time rule (one shared
        # definition: conformal.recall_at_1), vectorized over the trace
        recall: Dict[float, list] = {}
        scored = ([] if recall_oracle is None else
                  [(rid, c) for rid, c in completions.items()
                   if rid in recall_oracle])
        if scored:
            hits = np.asarray(conformal.recall_at_1(
                np.asarray([c["result"]["dist"] for _, c in scored],
                           np.float32),
                np.asarray([recall_oracle[rid] for rid, _ in scored],
                           np.float32))) > 0
            for (rid, c), hit in zip(scored, hits):
                self.telemetry.observe_recall(c["target"], bool(hit))
                observe_recall_cell(recall, c["target"], bool(hit))
        n_valid = sum(b["n_valid"] for b in batch_log)
        n_slots = sum(b["bucket"] for b in batch_log)
        n_leaves = (next(iter(completions.values()))["result"]["n_leaves"]
                    if completions else 0)
        report = {
            "n_requests": len(completions),
            "n_batches": len(batch_log),
            "padding_fraction": (n_slots - n_valid) / max(n_slots, 1),
            "pruning_ratio": (1.0 - float(np.mean(searched)) / n_leaves
                              if searched and n_leaves else float("nan")),
            "recall_by_target": recall_summary(recall),
        }
        report.update(latency_percentiles(lats))
        if completions:
            first = min(r.arrival for r in trace)
            last = max(c["finish"] for c in completions.values())
            report["throughput_qps"] = len(completions) / max(last - first,
                                                              1e-12)
            report["makespan_s"] = last - first
        report["n_programs_warmed"] = len(self._warmed)
        if self.shadow is not None and self.shadow.pending_count:
            # off the critical path by construction: every completion above
            # is already timed/committed before the exact scans run
            shadow_report = self.shadow.drain()
            self.telemetry.record_shadow(shadow_report)
            report["shadow"] = shadow_report
        report["batches"] = batch_log
        report["completions"] = completions
        return report
