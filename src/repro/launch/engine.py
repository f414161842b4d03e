"""Serving engine internals: executor, micro-batch engine, async runtime.

This module is the scheduler/executor half of the serving stack
(DESIGN.md §13; the admission/policy half lives in
`repro.launch.admission`, fault injection in `repro.launch.faults`, the
CLI and stream driver in `repro.launch.serve`):

  * :class:`CascadeExecutor` — the **executor layer**: owns the table or
    `repro.store` handle, the calibrated (eps, delta) plan and the jitted
    fused-cascade flush function for ONE eps point; `dispatch` serves a
    padded lane buffer in a single kernel launch and `sync_store`
    re-derives the plan only when the store's capacity or value range
    outgrows it.
  * :class:`MIPSServeEngine` — the PR-2 micro-batching request loop,
    now a thin scheduler over one `CascadeExecutor` (behaviour and stats
    unchanged: batch/deadline triggers, `QuantizedLRU`, live-store
    draining).
  * :class:`ServeRuntime` — the continuous-batching runtime: a bounded
    priority queue (`AdmissionController`) feeds fixed kernel lanes that
    are *refilled between dispatches* (work-conserving: once the
    executor is busy, freed lanes take whatever is queued instead of
    waiting out the batch deadline), a `DegradationLadder` of
    precompiled executors relaxes eps toward a configured floor under
    queue pressure before anything is refused, dispatch is wrapped in
    retry-with-backoff + poison quarantine so a bad micro-batch can
    never kill the engine, and `stats()` exports p50/p95/p99 latency,
    queue depth, shed/reject/retry/degraded counters and per-dispatch
    lane accounting.

Every request submitted to `ServeRuntime` terminates as a typed
`repro.launch.admission.ServeResult` — ``ok``/``degraded`` with answers
meeting the recorded ``eps_served``, or ``rejected``/``overloaded``/
``failed`` refusals.  The runtime never raises on traffic.
"""

from __future__ import annotations

import collections
import contextvars
import dataclasses
import struct
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.admission import (AdmissionController, DegradationLadder,
                                    PriorityClass, ServeResult, Ticket)
from repro.obs.metrics import (PULL_FRAC_BUCKETS, MetricsRegistry,
                               summarize_latencies)
from repro.obs.trace import counting_compiles, phase

__all__ = ["QuantizedLRU", "CascadeExecutor", "MIPSServeEngine",
           "ServeRuntime", "DispatchFailed", "dispatch_with_retries"]


class DispatchFailed(RuntimeError):
    """A dispatch exhausted its retry budget (`dispatch_with_retries`).

    Carries the last ``cause`` exception, the number of ``retries``
    burned and the accumulated virtual ``backoff`` seconds so the
    caller can fail the batch with honest accounting.
    """

    def __init__(self, cause: Exception, retries: int, backoff: float):
        super().__init__(f"dispatch failed after {retries} retries: {cause}")
        self.cause = cause
        self.retries = retries
        self.backoff = backoff


#: the runtime's index of the dispatch in flight, which the executor's
#: ``repro.executor.*`` spans carry (-1 outside `dispatch_with_retries`);
#: a context variable, so that ``dispatch(Qbuf, key)`` keeps its signature
#: for every caller and wrapper of it
_DIDX = contextvars.ContextVar("repro_didx", default=-1)
#: the lanes that the dispatch in flight fills, set beside ``_DIDX``;
#: None (a direct ``dispatch`` call, a warm-up) counts every lane
_FILLED = contextvars.ContextVar("repro_filled", default=None)


def _slab_bytes(plan, table_itemsize: int) -> float:
    """Bytes of one ``tile x block`` pull as the table is stored.

    The unquantized tier reads the table at its own width (4 bytes an
    element for fp32, 2 for bf16); a quantized tier reads its codes
    (int8 1 byte, int4 half of one, pq one byte per ``pq_subdims``).
    """
    per_row = {"fp32": float(table_itemsize) * plan.block,
               "int8": 1.0 * plan.block, "int4": 0.5 * plan.block,
               "pq": float(plan.block // plan.pq_subdims)}[plan.precision]
    return plan.tile * per_row


def dispatch_with_retries(ex, Qbuf, key, *, didx: int,
                          filled: Optional[int] = None, injector=None,
                          max_retries: int = 2,
                          retry_backoff_s: float = 1e-3,
                          on_error=None, on_retry=None):
    """One executor dispatch under the runtime's fault contract.

    Runs ``ex.dispatch(Qbuf, key)`` with exponential-backoff retries,
    consulting the deterministic fault ``injector`` (attempt-level
    injected errors, post-success latency spikes) exactly like
    `ServeRuntime` always has; extracted so the multi-tenant runtime
    (`repro.launch.tenancy`) shares one implementation instead of
    drifting.  ``on_error(exc, attempt, injected)`` fires per failing
    attempt, ``on_retry(attempt, backoff)`` per retry decision — both
    before the backoff grows.  Returns ``(ids, scores, rounds, dt,
    retries, backoff, spike)`` where ``dt`` already includes the
    injected ``spike`` and accumulated ``backoff`` (virtual seconds);
    raises `DispatchFailed` past ``max_retries``.  ``filled`` is the
    number of lanes of ``Qbuf`` that hold requests (default: all), which
    the executor's byte counter and launch span read.
    """
    attempt = 0
    backoff = 0.0
    token = _DIDX.set(didx)
    filled_token = _FILLED.set(filled)
    try:
        while True:
            injected = (injector.dispatch_error(didx, attempt)
                        if injector is not None else None)
            try:
                if injected is not None:
                    raise injected
                ids, scores, rounds, dt = ex.dispatch(Qbuf, key)
                break
            except Exception as e:
                if on_error is not None:
                    on_error(e, attempt, injected is not None)
                if attempt >= max_retries:
                    raise DispatchFailed(e, attempt, backoff) from e
                if on_retry is not None:
                    on_retry(attempt, backoff)
                backoff += retry_backoff_s * (2.0 ** attempt)
                attempt += 1
    finally:
        _DIDX.reset(token)
        _FILLED.reset(filled_token)
    spike = injector.latency_s(didx) if injector is not None else 0.0
    return ids, scores, rounds, dt + spike + backoff, attempt, backoff, spike


class QuantizedLRU:
    """LRU result cache keyed on quantized queries.

    Keys are the bytes of ``round(q / resolution)`` (int32): any two
    queries within ``resolution`` per coordinate share a cache line, which
    is exactly the granularity at which an (eps, delta)-approximate answer
    is reusable.  ``resolution=0`` disables quantization sharing (exact
    byte equality only).  Capacity 0 disables the cache entirely.
    """

    def __init__(self, capacity: int, resolution: float = 1e-3):
        self.capacity = int(capacity)
        self.resolution = float(resolution)
        self._od: "collections.OrderedDict[bytes, object]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.invalidations = 0

    def key(self, q: np.ndarray) -> bytes:
        """Quantize a (N,) query to its cache key."""
        if self.resolution > 0:
            return np.round(np.asarray(q, np.float32)
                            / self.resolution).astype(np.int64).tobytes()
        return np.asarray(q, np.float32).tobytes()   # exact bytes only

    def get(self, key: bytes):
        """Return the cached value or None; counts the hit/miss."""
        hit = self._od.get(key)
        if hit is None:
            self.misses += 1
            return None
        self._od.move_to_end(key)
        self.hits += 1
        return hit

    def put(self, key: bytes, value) -> None:
        """Insert/update; evicts the least-recently-used past capacity."""
        if self.capacity <= 0:
            return
        self._od[key] = value
        self._od.move_to_end(key)
        while len(self._od) > self.capacity:
            self._od.popitem(last=False)

    def invalidate(self) -> None:
        """Drop every entry (table version bump: cached answers are stale).

        Hit/miss counters survive; ``invalidations`` counts the calls.
        The engine additionally salts its keys with the table version, so
        even an entry that somehow survived an invalidation could never
        answer a post-update query.
        """
        self._od.clear()
        self.invalidations += 1

    def __len__(self) -> int:
        return len(self._od)


@dataclasses.dataclass
class _Pending:
    req_id: int
    q: np.ndarray
    t_submit: float
    cache_key: Optional[bytes]


class CascadeExecutor:
    """The executor layer: one calibrated (eps, delta) dispatch path.

    Owns the item table — a static array, a device-put sharded copy
    under ``mesh``, or a live `repro.store.DynamicTableStore` /
    `ShardedTableStore` — plus the `make_plan` calibration and the
    jitted single-dispatch flush function for exactly one eps point.
    Schedulers (`MIPSServeEngine`, `ServeRuntime`) own queues, caches
    and results; the executor only knows how to serve a full lane
    buffer:

      * `dispatch` runs one fused-cascade launch over a padded
        ``(lanes, N)`` query buffer (donated to jit so steady state
        recycles the device allocation) and returns host arrays plus
        the measured compute seconds;
      * `sync_store` re-derives the plan when the store's capacity or
        monotonic value range outgrows the calibrated bound — the only
        recompile-triggering events on the dynamic path (counted in
        ``n_recalibrations``);
      * `recall_of` rescoring a query exhaustively against the live
        table (the engine's sampled recall estimator).

    A `ServeRuntime` holds one executor per degradation-ladder rung —
    they share the same table/store object, so a rung switch costs
    nothing but the (already compiled) alternative flush function.
    """

    def __init__(self, table, *, K: int = 1, eps: float = 0.1,
                 delta: float = 0.1, value_range: Optional[float] = None,
                 qmax_hint: float = 1.0, tile: int = 8, block: int = 512,
                 lanes: int = 8, mesh=None, model_axis: str = "model",
                 n_valid: Optional[int] = None,
                 use_pallas: Optional[bool] = None,
                 precision: str = "fp32", range_slack: float = 1.0,
                 adaptive: bool = False, bound: str = "hoeffding",
                 pull_mode: str = "row", coord_block: int = 128,
                 quant_err: Optional[float] = None,
                 pq_subdims: int = 8, pq_codes: int = 16,
                 metrics: Optional[MetricsRegistry] = None,
                 metrics_labels: Optional[Dict[str, str]] = None):
        from repro.core.mips import table_abs_max
        from repro.store import DynamicTableStore, ShardedTableStore

        self.store = table if isinstance(
            table, (DynamicTableStore, ShardedTableStore)) else None
        self._qmax_hint = float(qmax_hint)
        self._range_slack = float(range_slack)
        self._use_pallas = use_pallas
        if self.store is not None:
            store = self.store
            if isinstance(store, ShardedTableStore):
                if mesh is not None and mesh is not store.mesh:
                    raise ValueError("mesh differs from the store's mesh")
                mesh = store.mesh
                model_axis = store.model_axis
            elif mesh is not None:
                raise ValueError(
                    "serving a mesh needs a ShardedTableStore")
            if n_valid is not None:
                raise ValueError("n_valid is store-managed")
            # the store owns the kernel geometry (its quantized shadow and
            # the executor's plan must agree tile-for-tile)
            tile, block = store.tile, store.block
            if store.precision != "fp32":
                precision = store.precision
                if store.precision == "pq":
                    pq_subdims = store.pq_subdims
                    pq_codes = store.pq_codes
            n, N = store.capacity_rows, store.N
            # clamp to the store's observed range exactly as sync_store
            # would on growth: a churned executor and a fresh executor on
            # the store's snapshot then always calibrate identical plans
            # (range_slack=1.0)
            floor = 2.0 * self._qmax_hint * max(store.value_abs_max, 1e-30)
            value_range = (floor if value_range is None
                           else max(float(value_range), floor))
        else:
            self._table = jnp.asarray(table)
            n, N = self._table.shape
            if value_range is None:
                # a-priori product-range bound: callers who know their
                # query norms should pass an explicit value_range instead
                value_range = 2.0 * qmax_hint * table_abs_max(self._table)
        self.n, self.N, self.K = n, N, K
        self.lanes = int(lanes)
        self.mesh = mesh
        self._model_axis = model_axis
        self.eps, self.delta = float(eps), float(delta)
        self._tile, self._block = int(tile), min(int(block), N)
        self.precision = precision
        self.adaptive = bool(adaptive)
        self._bound = bound
        self.pull_mode = pull_mode
        self._coord_block = int(coord_block)
        self._n_valid = n_valid
        self._quant_err = quant_err
        self._pq_subdims = int(pq_subdims)
        self._pq_codes = int(pq_codes)
        self._use_shadow = (self.store is not None and mesh is None
                            and self.store.precision != "fp32")
        if self._use_shadow and pull_mode != "row":
            # the store's incrementally maintained quantized shadow
            # (int8/int4 scales, pq codes) is encoded at the store's own
            # (tile, block) cells; a coord (or coord-resolvable hybrid)
            # plan re-blocks the feature axis at coord_block width, which
            # the shadow cannot serve.  fp32 stores and sharded stores
            # (which quantize in-jit at the plan's geometry) support
            # every pull mode.
            raise ValueError(
                f"pull_mode={pull_mode!r} is incompatible with a "
                f"single-device {self.store.precision} store shadow "
                f"(its quantization cells are fixed at the store's block "
                f"width); use pull_mode='row', an fp32 store, or a "
                f"ShardedTableStore")
        # cascade_* metrics: one labeled row per executor identity so a
        # ladder of rung executors shares metric families without
        # colliding (the runtime adds a "rung" label via metrics_labels)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        lbl = {"precision": str(self.precision),
               "pull_mode": str(self.pull_mode),
               "eps": f"{self.eps:.6g}"}
        for k, v in (metrics_labels or {}).items():
            lbl[str(k)] = str(v)
        self._mlabels = lbl
        keys = tuple(lbl)
        self._c_dispatch = self.metrics.counter(
            "cascade_dispatches_total",
            "Fused-cascade kernel launches (includes warmup).", keys)
        self._c_recal = self.metrics.counter(
            "cascade_recalibrations_total",
            "Plan re-derivations triggered by store growth.", keys)
        self._h_dispatch = self.metrics.histogram(
            "cascade_dispatch_ms",
            "Measured blocking compute time per dispatch (ms).", keys)
        self._c_compiles = self.metrics.counter(
            "cascade_compiles_total",
            "XLA compiles run inside a dispatch's launch.", keys)
        self._c_pulls = self.metrics.counter(
            "cascade_pulls_total",
            "Lane-pulls of the plan's flat schedule, per dispatch.", keys)
        self._c_dense_pulls = self.metrics.counter(
            "cascade_dense_pulls_total",
            "Lane-pulls the kernel's dense round-1 phase serves.", keys)
        self._c_pull_groups = self.metrics.counter(
            "cascade_pull_groups_total",
            "Lane grid steps that pull a group of same-column survivor "
            "tiles after round 1, per dispatch.", keys)
        self._c_read_bytes = self.metrics.counter(
            "cascade_table_read_bytes_total",
            "Table bytes the plan reads a dispatch, at the stored width: "
            "round 1 once for the batch, later pulls per filled lane.",
            keys)
        for c in (self._c_dispatch, self._c_recal, self._c_compiles,
                  self._c_pulls, self._c_dense_pulls, self._c_pull_groups,
                  self._c_read_bytes):
            c.seed(**lbl)
        self._seen_version = (0 if self.store is None
                              else self.store.version)
        self._table_np = None   # host copy, materialized only for recall

        self._build(float(value_range))   # sets plan (+ shard geometry)
        if mesh is not None and self.store is None:
            from repro.distributed.specs import serving_table_sharding
            n_valid_eff = n if n_valid is None else n_valid
            self._n_valid = n_valid_eff   # recall must mask pad rows too
            if self._n_pad:  # ragged: pad rows host-side ONCE, pre-placing
                self._table = jnp.pad(self._table,
                                      ((0, self._n_pad), (0, 0)))
            self._table = jax.device_put(
                self._table, serving_table_sharding(mesh, model_axis))
            # static per-shard validity prefixes, passed traced per flush
            self._nv_static = np.clip(
                n_valid_eff
                - np.arange(mesh.shape[model_axis]) * self._n_local,
                0, self._n_local).astype(np.int32)
        elif mesh is None:
            nv = n if n_valid is None else n_valid
            self._nv_static = np.int32(nv)

    @property
    def n_recalibrations(self) -> int:
        """Schedule re-derivations observed (registry-backed)."""
        return int(self._c_recal.get(**self._mlabels))

    def _build(self, value_range: float) -> None:
        """(Re)build the static plan + jitted flush fn for a value range.

        Called once at construction and again only when `sync_store`
        observes the store's capacity or monotonic value range outgrowing
        the calibrated bound — the only events that change the schedule
        (and therefore recompile) on the dynamic path.
        """
        from repro.core.boundedme_jax import (bounded_me_decode,
                                              fused_flat_schedule,
                                              fused_pull_group, make_plan)

        self._plan_value_range = float(value_range)
        mesh, model_axis = self.mesh, self._model_axis
        K, eps, delta = self.K, self.eps, self.delta
        tile, block = self._tile, self._block
        precision, use_pallas = self.precision, self._use_pallas
        adaptive, bound = self.adaptive, self._bound
        pull_mode, coord_block = self.pull_mode, self._coord_block
        pq_subdims, pq_codes = self._pq_subdims, self._pq_codes
        quant_err = self._quant_err
        if precision == "pq" and quant_err is None:
            # pq has no a-priori worst-case model: calibrate a measured
            # per-pull bound on the served table (re-measured at every
            # rebuild event, so growth/refresh re-anchor it).  Hybrid
            # plans price two pull widths with different codebooks; take
            # the conservative max across candidate widths.
            from repro.core.boundedme_jax import measured_plan_quant_err
            V_cal = (self.store.host_table() if self.store is not None
                     else self._table)
            widths = {"row": (block,), "coord": (coord_block,),
                      "hybrid": (block, coord_block)}[pull_mode]
            quant_err = max(measured_plan_quant_err(
                V_cal, precision="pq", tile=tile, block=w,
                pq_subdims=pq_subdims, pq_codes=pq_codes)
                for w in widths)
        if mesh is not None:
            from repro.distributed.sharding import (make_shard_plan,
                                                    sharded_bounded_me_decode)
            self.plan, self._n_local, self._n_pad, _ = make_shard_plan(
                self.n, self.N, mesh.shape[model_axis], K=K, eps=eps,
                delta=delta, value_range=value_range, tile=tile, block=block,
                precision=precision, bound=bound, pull_mode=pull_mode,
                coord_block=coord_block, quant_err=quant_err,
                pq_subdims=pq_subdims, pq_codes=pq_codes)

            def _flush_fn(tbl, Qbuf, key, nv):
                out = sharded_bounded_me_decode(
                    tbl, Qbuf, key, mesh=mesh, K=K, model_axis=model_axis,
                    n_valid=nv, eps=eps, delta=delta,
                    value_range=value_range, tile=tile, block=block,
                    final_exact=True, use_pallas=use_pallas,
                    precision=precision, adaptive=adaptive, bound=bound,
                    pull_mode=pull_mode, coord_block=coord_block,
                    quant_err=quant_err, pq_subdims=pq_subdims,
                    pq_codes=pq_codes)
                # rounds_used is (B, shards) when adaptive, else absent
                return out[0], out[1], (out[3] if adaptive else None)

            donate = 1
        else:
            plan = make_plan(self.n, self.N, K=K, eps=eps, delta=delta,
                             value_range=value_range, tile=tile,
                             block=block, precision=precision, bound=bound,
                             pull_mode=pull_mode, coord_block=coord_block,
                             quant_err=quant_err, pq_subdims=pq_subdims,
                             pq_codes=pq_codes)
            self.plan = plan
            if self._use_shadow:
                # the store maintains the quantized shadow incrementally
                # (int8/int4 codes + scales, or pq codes + codebook); the
                # flush consumes it instead of re-encoding the table
                def _flush_fn(tbl, Vq, vaux, Qbuf, key, nv):
                    out = bounded_me_decode(
                        tbl, Qbuf, key, plan=plan, final_exact=True,
                        use_pallas=use_pallas, n_valid=nv,
                        quantized=(Vq, vaux), adaptive=adaptive)
                    return (out if adaptive else (*out, None))

                donate = 3
            else:
                def _flush_fn(tbl, Qbuf, key, nv):
                    # padding/dead rows are masked inside the cascade, so
                    # they can never occupy the returned top-K slots
                    out = bounded_me_decode(
                        tbl, Qbuf, key, plan=plan, final_exact=True,
                        use_pallas=use_pallas, n_valid=nv, adaptive=adaptive)
                    return (out if adaptive else (*out, None))

                donate = 1

        # per-dispatch pull counts of the plan (one shard's under a mesh)
        flat = fused_flat_schedule(self.plan, final_exact=True,
                                   adaptive=adaptive)
        self._lane_pulls = self.lanes * flat.pull_steps
        self._lane_dense_pulls = self.lanes * flat.dense_pulls
        # table bytes a dispatch reads: the dense phase's slabs once for
        # the batch, then each filled lane's own pulls
        dtype = jnp.dtype(self.table.dtype)
        _, size = flat.groups(fused_pull_group(self.plan, dtype))
        self._lane_pull_groups = self.lanes * int((size > 0).sum())
        self._table_dtype = dtype.name
        slab = _slab_bytes(self.plan, dtype.itemsize)
        self._dense_bytes = int(flat.dense_pulls * slab)
        self._query_bytes = int((flat.pull_steps - flat.dense_pulls) * slab)

        # donate the query buffer: steady-state flushes recycle the same
        # (lanes, N) device allocation (no-op on backends without
        # donation support, e.g. CPU)
        self._fn = jax.jit(_flush_fn, donate_argnums=(donate,))

    def sync_store(self) -> int:
        """Re-derive the plan if the store outgrew it; returns rebuilds.

        Checks, in order: a version change drops the stale recall
        mirror; capacity growth (``grow()``) rebuilds plan + flush fn at
        the new shapes; monotonic value-range growth past the calibrated
        bound re-derives the schedule at ``range * range_slack``.  The
        two rebuild events are the only recompile triggers on the
        dynamic path, counted in ``n_recalibrations``.  No-op without a
        store.
        """
        store = self.store
        if store is None:
            return 0
        rebuilt = 0
        if store.version != self._seen_version:
            self._seen_version = store.version
            self._table_np = None   # never serve stale recall ground truth
        if store.capacity_rows != self.n:
            # the store grew: shapes changed, rebuild plan + flush fn
            self.n = store.capacity_rows
            self._build(self._plan_value_range)
            rebuilt += 1
        needed = 2.0 * self._qmax_hint * store.value_abs_max
        if needed > self._plan_value_range:
            # value-range growth is the only other event that re-derives
            # the schedule; range_slack > 1 buys headroom so a growing
            # corpus recalibrates O(log growth) times, not per update
            self._build(needed * self._range_slack)
            rebuilt += 1
        if rebuilt:
            self._c_recal.inc(rebuilt, **self._mlabels)
        return rebuilt

    def _flush_args(self, Qbuf, key):
        """Assemble per-flush operands (table/shadow/validity) in order."""
        store = self.store
        if store is None:
            return (self._table, Qbuf, key, self._nv_static)
        tbl = store.device_table()
        if self.mesh is not None:
            nv = store.n_valid_vector()
        else:
            nv = np.int32(store.n_live)
        if self._use_shadow:
            Vq, vaux = store.quantized()
            return (tbl, Vq, vaux, Qbuf, key, nv)
        return (tbl, Qbuf, key, nv)

    def dispatch(self, Qbuf: np.ndarray, key) -> Tuple[
            np.ndarray, np.ndarray, Optional[np.ndarray], float]:
        """Serve one padded (lanes, N) buffer in a single kernel launch.

        Returns ``(ids, scores, rounds_used, seconds)`` as host arrays
        (``rounds_used`` is None unless adaptive); ``seconds`` is the
        measured blocking compute time, which virtual-clock drivers add
        to their clock.  Raises whatever the dispatch raises — callers
        (the runtime's retry wrapper) own failure policy.

        Each step is a `repro.obs.trace.phase` span carrying the
        runtime's ``didx``: ``executor.h2d`` (query buffer to the
        device), ``executor.launch`` (the jitted flush call, where any
        compile lands and is counted in ``cascade_compiles_total``),
        ``executor.wait`` (``block_until_ready``) and ``executor.d2h``
        (results to the host).  ``seconds`` spans the first three.  The
        launch span also carries ``lanes_filled`` (the lanes the runtime
        filled, `dispatch_with_retries`'s ``filled``; all of them for a
        direct call) and the stored ``table_dtype``.
        """
        didx = _DIDX.get()
        filled = _FILLED.get()
        filled = self.lanes if filled is None else filled
        t0 = time.perf_counter()
        with warnings.catch_warnings():
            # CPU backends warn that donation is unimplemented; harmless
            warnings.filterwarnings("ignore",
                                    message=".*[Dd]onat.*")
            with phase("executor.h2d", didx=didx):
                args = self._flush_args(jnp.asarray(Qbuf), key)
            with phase("executor.launch", didx=didx, lanes_filled=filled,
                       table_dtype=self._table_dtype), \
                    counting_compiles(self._c_compiles, **self._mlabels):
                ids, scores, rounds = self._fn(*args)
            with phase("executor.wait", didx=didx):
                jax.block_until_ready(scores)
        dt = time.perf_counter() - t0
        self._c_dispatch.inc(**self._mlabels)
        self._c_pulls.inc(self._lane_pulls, **self._mlabels)
        self._c_dense_pulls.inc(self._lane_dense_pulls, **self._mlabels)
        self._c_pull_groups.inc(self._lane_pull_groups, **self._mlabels)
        self._c_read_bytes.inc(self._dense_bytes + filled * self._query_bytes,
                               **self._mlabels)
        self._h_dispatch.observe(dt * 1e3, **self._mlabels)
        with phase("executor.d2h", didx=didx):
            return (np.asarray(ids), np.asarray(scores),
                    None if rounds is None else np.asarray(rounds), dt)

    @property
    def table(self):
        """The device table the flush reads (row-sharded under a mesh)."""
        return self._table if self.store is None else self.store.device_table()

    def compiled_text(self) -> str:
        """HLO text of the compiled flush program for a full lane buffer.

        A flush served by the fused kernel contains ``tpu_custom_call``;
        one served by the jnp fallback does not.
        """
        Qbuf = jnp.zeros((self.lanes, self.N), jnp.float32)
        args = self._flush_args(Qbuf, jax.random.PRNGKey(0))
        return self._fn.lower(*args).compile().as_text()

    def recall_of(self, q: np.ndarray, got_slots: np.ndarray) -> float:
        """Exact-top-K overlap of a served answer (host rescore)."""
        if self.store is not None:
            # the store's host mirror is updated in O(rows touched) at
            # every apply_updates, so live recall never goes stale
            tbl = self.store.host_table()
            s = tbl @ q
            s[~self.store.live_mask()] = -np.inf
        else:
            if self._table_np is None:
                self._table_np = np.asarray(self._table)
            s = self._table_np @ q
            if self._n_valid is not None:
                s[self._n_valid:] = -np.inf
        exact = np.argpartition(-s, self.K - 1)[:self.K]
        return len(set(exact.tolist()) & set(got_slots.tolist())) / self.K

    def external_ids(self, slots: np.ndarray) -> np.ndarray:
        """Map cascade slots to stable external ids (store) or copy."""
        if self.store is not None:
            return self.store.external_ids(slots)
        return slots.copy()

    @property
    def plan_value_range(self) -> float:
        """The value range the current plan was calibrated at.

        The registry's executor cache (`repro.launch.tenancy`) compares
        ``2 * qmax_hint * store.value_abs_max`` against this to decide
        whether a cached ladder is still a valid bound or must be
        rebuilt (the range-recalibration salt of the cache key).
        """
        return self._plan_value_range

    @property
    def qmax_hint(self) -> float:
        """The |q| bound the value-range calibration assumes."""
        return self._qmax_hint


class MIPSServeEngine:
    """Micro-batching MIPS request loop over a fixed item table.

    Requests (`submit`) are answered from the LRU when a quantized-equal
    query was served recently; otherwise they queue until either
    ``batch_size`` requests are waiting or the oldest has aged past
    ``deadline_ms`` (`poll` applies both triggers), then the whole
    micro-batch is served by ONE fused-cascade dispatch through a
    `CascadeExecutor`.  The padded (batch_size, N) query buffer is
    donated to jit so steady-state serving re-uses its device allocation
    instead of growing one per flush.

    With ``mesh`` the flush runs `sharded_bounded_me_decode` (shard-local
    cascades + exact cross-shard merge, DESIGN.md §7); otherwise the
    single-device `bounded_me_decode`.  Results arrive via `result` as
    ``(ids (K,), scores (K,))`` numpy arrays.

    ``recall_sample_rate`` > 0 additionally rescoring a random fraction of
    requests exhaustively on the host and folds top-K recall into
    `stats` — the live accuracy counter for the (eps, delta) knob.

    ``precision='int8'`` serves every flush on int8-quantized tiles under
    quantization-widened confidence bounds (DESIGN.md §10, docs/TUNING.md):
    roughly half the sampling-phase memory traffic per flush, with returned
    scores still fp32-exact (candidate rescore).  The live ``recall``
    stat is the operator's check that the widened (eps, delta) calibration
    holds on real traffic.

    ``adaptive=True`` (DESIGN.md §12) lets every query in a flush certify
    early exit at round boundaries under the ``bound`` radius family
    ('hoeffding' reuses the schedule's events; 'bernstein' is
    variance-aware): easy queries stop pulling rounds early inside the
    same (eps, delta) contract, and `stats()["adaptive"]` exports the
    per-query ``rounds_used`` histogram plus the mean executed-pull
    fraction.  Works on every path — single-device, sharded
    (shard-local certification), and store-backed including the int8
    shadow (certification radii carry the quantization bias).

    ``pull_mode`` selects the reward stream per flush (DESIGN.md §14):
    'row' (default), 'coord' (the BanditMIPS coordinate estimator —
    narrow ``coord_block``-wide feature tiles, certified pull cost
    sublinear in d; best for high-dimensional embedding tables) or
    'hybrid' (the executor prices both candidate plans and serves the
    cheaper, row-preferred within a 10% multiply margin).  One
    incompatibility, rejected at construction: a single-device quantized
    store shadow (int8/int4/pq) fixes the quantization-block geometry,
    so it serves ``pull_mode='row'`` only.

    **Live corpora** (DESIGN.md §11): ``table`` may be a
    `repro.store.DynamicTableStore` (or `ShardedTableStore` for
    multi-device serving) instead of a static array.  The engine then
    serves the store's preallocated capacity buffer with the live-row
    count riding in as a traced ``n_valid`` every flush, so
    upsert/delete/append streams recompile nothing; staged mutations are
    drained by `apply_updates` — called automatically at every `poll` /
    `drain`, i.e. between micro-batch flushes — which also bumps the
    engine's table version (salting + invalidating the LRU so no stale
    answer survives), keeps the recall estimator on the store's live host
    mirror, and re-derives the (eps, delta) schedule only when the
    store's monotonic value range grows past the calibrated bound.
    Returned ids are the store's stable *external* ids.  The engine
    adopts the store's ``tile``/``block`` geometry and (for a
    `DynamicTableStore` quantized shadow — int8, int4 or pq) its
    ``precision`` and pq codebook geometry; a pq plan's measured
    ``quant_err`` is auto-calibrated on the served table unless passed
    explicitly.

    Failure modes: queries must be (N,) float and finite — NaN/inf
    propagate into scores and poison the LRU line; `submit` raises on a
    shape mismatch.  The engine is not thread-safe; drive it from one
    loop.  (`ServeRuntime` is the hardened front: typed refusals instead
    of exceptions, admission control, overload shedding.)
    """

    def __init__(self, table, *, K: int = 1, eps: float = 0.1,
                 delta: float = 0.1, value_range: Optional[float] = None,
                 qmax_hint: float = 1.0, tile: int = 8, block: int = 512,
                 batch_size: int = 8, deadline_ms: float = 2.0,
                 cache_entries: int = 512, cache_resolution: float = 1e-3,
                 mesh=None, model_axis: str = "model",
                 n_valid: Optional[int] = None,
                 recall_sample_rate: float = 0.0,
                 use_pallas: Optional[bool] = None,
                 precision: str = "fp32", range_slack: float = 1.0,
                 adaptive: bool = False, bound: str = "hoeffding",
                 pull_mode: str = "row", coord_block: int = 128,
                 quant_err: Optional[float] = None,
                 pq_subdims: int = 8, pq_codes: int = 16,
                 seed: int = 0,
                 metrics: Optional[MetricsRegistry] = None):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._exec = CascadeExecutor(
            table, K=K, eps=eps, delta=delta, value_range=value_range,
            qmax_hint=qmax_hint, tile=tile, block=block, lanes=batch_size,
            mesh=mesh, model_axis=model_axis, n_valid=n_valid,
            use_pallas=use_pallas, precision=precision,
            range_slack=range_slack, adaptive=adaptive, bound=bound,
            pull_mode=pull_mode, coord_block=coord_block,
            quant_err=quant_err, pq_subdims=pq_subdims, pq_codes=pq_codes,
            metrics=self.metrics)
        self.K = K
        self.batch_size = int(batch_size)
        self.deadline_s = float(deadline_ms) * 1e-3
        self._eps, self._delta = float(eps), float(delta)
        self._adaptive = bool(adaptive)
        self._bound = bound
        self._key = jax.random.PRNGKey(seed)
        self.cache = QuantizedLRU(cache_entries, cache_resolution)
        self._store = self._exec.store
        self._version = 0 if self._store is None else self._store.version
        self._pending: List[_Pending] = []
        self._results: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._next_id = 0
        self._recall_rate = float(recall_sample_rate)
        self._recall_rng = np.random.default_rng(seed)
        self._lat: List[float] = []
        self._recalls: List[float] = []
        self._rounds: List[int] = []   # adaptive: per-query exit rounds
        if self._store is not None:
            self.metrics.adopt(self._store.metrics)
        self._c_requests = self.metrics.counter(
            "serve_requests_total", "Requests submitted.")
        self._c_cache_hits = self.metrics.counter(
            "serve_cache_hits_total", "Requests answered from the LRU.")
        self._c_batches = self.metrics.counter(
            "serve_batches_total", "Micro-batch flushes by trigger.",
            ("trigger",))
        self._c_batches.seed(trigger="full")
        self._c_batches.seed(trigger="deadline")
        self._c_update_rows = self.metrics.counter(
            "serve_update_rows_total", "Store mutations applied.")
        self._c_update_flushes = self.metrics.counter(
            "serve_update_flushes_total", "Store flush_updates calls.")
        self._h_latency = self.metrics.histogram(
            "serve_latency_ms", "Per-request latency (ms), cache hits at 0.")
        self._h_occupancy = self.metrics.histogram(
            "serve_batch_occupancy", "Filled lanes per micro-batch flush.",
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0))
        self.metrics.gauge(
            "serve_pending", "Requests accepted but not yet served.",
        ).set_fn(lambda: len(self._pending))
        self.metrics.gauge(
            "serve_cache_entries", "Live LRU cache entries.",
        ).set_fn(lambda: len(self.cache))
        #: plain dispatch sequence for PRNG fold keys — deliberately NOT
        #: registry-backed so metric wiring (or the NullRegistry hard-off
        #: switch) can never perturb sampling keys
        self._batch_seq = 0
        self._update_time_s = 0.0
        self._occupancy: List[int] = []

    # ---- legacy counter surface (registry-backed) ------------------------

    @property
    def n_requests(self) -> int:
        """Requests submitted (registry-backed)."""
        return int(self._c_requests.total())

    @property
    def n_cache_hits(self) -> int:
        """Cache-answered requests (registry-backed)."""
        return int(self._c_cache_hits.total())

    @property
    def n_batches(self) -> int:
        """Micro-batch flushes, all triggers (registry-backed)."""
        return int(self._c_batches.total())

    @property
    def n_full_flushes(self) -> int:
        """Flushes triggered by a full batch (registry-backed)."""
        return int(self._c_batches.get(trigger="full"))

    @property
    def n_deadline_flushes(self) -> int:
        """Flushes triggered by the batch deadline (registry-backed)."""
        return int(self._c_batches.get(trigger="deadline"))

    @property
    def n_updates(self) -> int:
        """Store mutations applied (registry-backed)."""
        return int(self._c_update_rows.total())

    @property
    def n_update_flushes(self) -> int:
        """Store flush_updates calls (registry-backed)."""
        return int(self._c_update_flushes.total())

    # ---- executor delegation (back-compat surface) -----------------------

    @property
    def n(self) -> int:
        """Row capacity of the served table (executor-owned)."""
        return self._exec.n

    @property
    def N(self) -> int:
        """Query/item dimensionality."""
        return self._exec.N

    @property
    def plan(self):
        """The executor's calibrated BlockedPlan."""
        return self._exec.plan

    @property
    def n_recalibrations(self) -> int:
        """Schedule re-derivations observed (executor-owned)."""
        return self._exec.n_recalibrations

    @property
    def _fn(self):
        return self._exec._fn

    @property
    def _plan_value_range(self) -> float:
        return self._exec._plan_value_range

    # ---- request path ---------------------------------------------------

    @property
    def pending_count(self) -> int:
        """Requests accepted but not yet served (excludes cache hits)."""
        return len(self._pending)

    def submit(self, q, now: Optional[float] = None) -> int:
        """Accept one (N,) query; returns its request id.

        Cache hits complete immediately (latency ~0); misses queue for the
        next micro-batch.  ``now`` (seconds, any monotonic origin) defaults
        to wall clock — pass a virtual clock for simulation.  Staged store
        mutations are drained first: a query submitted after an upsert
        must never be answered from a pre-upsert cache line or table.
        """
        q = np.asarray(q, np.float32)
        if q.shape != (self.N,):
            raise ValueError(f"query shape {q.shape} != ({self.N},)")
        self.apply_updates()
        now = time.perf_counter() if now is None else now
        rid = self._next_id
        self._next_id += 1
        self._c_requests.inc()
        # lookups are salted with the *current* (table version, K): a
        # result cached before an update can never answer a post-update
        # query, even if an invalidation were missed
        ck = self.cache.key(q) if self.cache.capacity > 0 else None
        if ck is not None:
            hit = self.cache.get(self._salted(ck))
            if hit is not None:
                self._results[rid] = hit
                self._c_cache_hits.inc()
                self._lat.append(0.0)
                self._h_latency.observe(0.0)
                return rid
        self._pending.append(_Pending(rid, q, now, ck))
        return rid

    def _salted(self, base_key: bytes) -> bytes:
        """Prefix an LRU base key with the live (version, K) salt."""
        return struct.pack("<qi", self._version, self.K) + base_key

    def poll(self, now: Optional[float] = None) -> Tuple[List[int], float]:
        """Flush micro-batches whose trigger fired; returns (ids, busy_s).

        Triggers: ``batch_size`` requests waiting (full flush), or the
        oldest pending request older than the batch deadline (deadline
        flush).  ``busy_s`` is the wall time spent in compute, so virtual-
        clock drivers can advance time by it.  Store-backed engines drain
        staged table mutations first (`apply_updates`), so a flush never
        serves a torn table and an update submitted before a query is
        visible to it.
        """
        now = time.perf_counter() if now is None else now
        self.apply_updates()
        done: List[int] = []
        busy = 0.0
        while self._pending:
            full = len(self._pending) >= self.batch_size
            aged = now - self._pending[0].t_submit >= self.deadline_s
            if not (full or aged):
                break
            self._c_batches.inc(trigger="full" if full else "deadline")
            ids, dt = self._flush(now + busy)
            done.extend(ids)
            busy += dt
        return done, busy

    def drain(self, now: Optional[float] = None) -> Tuple[List[int], float]:
        """Flush everything pending regardless of triggers (shutdown).

        Also drains staged store mutations first, like `poll`.
        """
        now = time.perf_counter() if now is None else now
        self.apply_updates()
        done: List[int] = []
        busy = 0.0
        while self._pending:
            self._c_batches.inc(trigger="deadline")
            ids, dt = self._flush(now + busy)
            done.extend(ids)
            busy += dt
        return done, busy

    def result(self, req_id: int):
        """Pop the (ids, scores) result for a completed request, or None."""
        return self._results.pop(req_id, None)

    # ---- updates (store-backed engines) ---------------------------------

    def apply_updates(self) -> int:
        """Drain the store's staged mutations; returns rows applied.

        Runs between micro-batch flushes (`poll` / `drain` call it first),
        so in-flight queries never observe a half-applied update burst.
        On any applied mutation: bumps the engine's table version (the
        LRU is invalidated and its keys salted so no pre-update answer
        survives), drops the stale recall mirror (the estimator reads the
        store's always-fresh host mirror anyway), and — only if the
        store's monotonic value range grew past the calibrated bound —
        re-derives the (eps, delta) schedule at ``range * range_slack``
        (the lone recompile-triggering event, counted in
        ``stats()["updates"]["recalibrations"]``).  No-op without a store.
        """
        store = self._store
        if store is None:
            return 0
        applied = 0
        if store.pending_updates:
            t0 = time.perf_counter()
            info = store.flush_updates()
            applied = info["applied"]
            self._c_update_rows.inc(applied)
            self._c_update_flushes.inc()
            self._update_time_s += time.perf_counter() - t0
        if store.version != self._version:
            # covers staged mutations AND out-of-band ones (grow())
            self._version = store.version
            self.cache.invalidate()
        self._exec.sync_store()
        return applied

    # ---- flush ----------------------------------------------------------

    def _flush(self, now: float) -> Tuple[List[int], float]:
        batch = self._pending[:self.batch_size]
        self._pending = self._pending[len(batch):]
        Qbuf = np.zeros((self.batch_size, self.N), np.float32)
        for i, p in enumerate(batch):
            Qbuf[i] = p.q
        # fold on the plain dispatch sequence, NOT a registry counter:
        # sampling keys must be invariant to observability wiring
        key = jax.random.fold_in(self._key, self._batch_seq)
        token = _FILLED.set(len(batch))
        try:
            ids, scores, rounds, dt = self._exec.dispatch(Qbuf, key)
        finally:
            _FILLED.reset(token)
        ids = ids[:len(batch)]
        scores = scores[:len(batch)]
        if rounds is not None:
            # (B,) single-device, (B, shards) sharded: histogram every
            # shard's exit round for the real (non-padding) batch rows
            self._rounds.extend(
                rounds[:len(batch)].reshape(-1).tolist())
        self._batch_seq += 1
        self._occupancy.append(len(batch))
        self._h_occupancy.observe(len(batch))
        done = []
        for i, p in enumerate(batch):
            # store-backed engines answer with stable external ids, never
            # raw slots (a slot's occupant changes across swap-deletes)
            res = (self._exec.external_ids(ids[i]), scores[i].copy())
            self._results[p.req_id] = res
            if p.cache_key is not None:
                # salt at put time: if the version bumped while this
                # request was queued, the result files under the live
                # version (not a dead pre-update key)
                self.cache.put(self._salted(p.cache_key), res)
            self._lat.append((now - p.t_submit) + dt)
            self._h_latency.observe(((now - p.t_submit) + dt) * 1e3)
            if (self._recall_rate > 0.0
                    and self._recall_rng.random() < self._recall_rate):
                self._recalls.append(self._exec.recall_of(p.q, ids[i]))
            done.append(p.req_id)
        if len(self._lat) > 100_000:       # bound the stats memory
            self._lat = self._lat[-10_000:]
        if len(self._occupancy) > 100_000:
            self._occupancy = self._occupancy[-10_000:]
        if len(self._recalls) > 100_000:
            self._recalls = self._recalls[-10_000:]
        if len(self._rounds) > 100_000:
            self._rounds = self._rounds[-10_000:]
        return done, dt

    # ---- observability --------------------------------------------------

    def _adaptive_stats(self) -> dict:
        """Early-exit telemetry: rounds_used histogram + mean pull frac."""
        out = {"enabled": self._adaptive, "bound": self._bound}
        if not self._adaptive:
            return out
        from repro.core.schedule import pulls_through_round
        hist: Dict[int, int] = {}
        for r in self._rounds:
            hist[int(r)] = hist.get(int(r), 0) + 1
        pulls = pulls_through_round(self.plan.schedule)
        total = max(1, int(pulls[-1]))
        samples = max(1, len(self._rounds))
        mean_pulls = sum(int(pulls[min(r, len(pulls) - 1)]) * c
                         for r, c in hist.items()) / samples
        out.update({
            "samples": len(self._rounds),
            "rounds_hist": {str(k): v for k, v in sorted(hist.items())},
            "mean_rounds": (float(np.mean(self._rounds))
                            if self._rounds else 0.0),
            "mean_pull_frac": mean_pulls / total,
        })
        return out

    def stats(self) -> dict:
        """Per-request latency/recall counters as a plain dict.

        latency_ms percentiles include cache hits (latency 0); recall is
        over the sampled fraction only (``nan`` when nothing was sampled).
        """
        occ = np.asarray(self._occupancy, np.float64)
        return {
            "requests": self.n_requests,
            "completed": self.n_requests - len(self._pending),
            "pending": len(self._pending),
            "batches": self.n_batches,
            "full_flushes": self.n_full_flushes,
            "deadline_flushes": self.n_deadline_flushes,
            "mean_batch_occupancy": float(occ.mean()) if occ.size else 0.0,
            "cache": {"hits": self.cache.hits, "misses": self.cache.misses,
                      "entries": len(self.cache),
                      "hit_rate": (self.cache.hits
                                   / max(1, self.cache.hits
                                         + self.cache.misses))},
            "latency_ms": summarize_latencies(
                self._lat, keys=("mean", "p50", "p95", "max")),
            "recall": {"samples": len(self._recalls),
                       "mean": (float(np.mean(self._recalls))
                                if self._recalls else float("nan"))},
            "plan": {"rounds": len(self.plan.schedule.rounds),
                     "pull_speedup": self.plan.schedule.speedup},
            "adaptive": self._adaptive_stats(),
            "updates": {
                "applied": self.n_updates,
                "update_flushes": self.n_update_flushes,
                "recalibrations": self.n_recalibrations,
                "version": self._version,
                "cache_invalidations": self.cache.invalidations,
                "rows_per_s": (self.n_updates / self._update_time_s
                               if self._update_time_s > 0 else 0.0)},
            **({"store": self._store.stats()}
               if self._store is not None else {}),
        }


class ServeRuntime:
    """Continuous-batching serving runtime with admission + degradation.

    The hardened request front (DESIGN.md §13).  Three layers:

      * **admission** (`repro.launch.admission.AdmissionController`):
        every `submit` is validated (poison NaN/Inf/wrong-dim queries are
        rejected at the door), checked against the quarantine, and
        enqueued into a bounded priority queue — a full queue refuses
        with a typed ``overloaded`` result or displaces lower-priority
        sheddable work;
      * **scheduler** (this class): `poll` assembles dispatch batches in
        (priority, FIFO) order onto ``lanes`` fixed kernel lanes and is
        *work-conserving* — while the executor is busy, freed lanes are
        refilled from the queue between dispatches instead of waiting
        out the batch deadline, so a burst drains at full lane
        occupancy.  Requests queued past their class deadline are shed
        (typed ``overloaded``/``deadline``) rather than served late;
      * **executor** (`CascadeExecutor`, one per degradation rung):
        under queue pressure the `DegradationLadder` relaxes eps toward
        ``eps_floor`` — each response records the ``eps_served`` it
        actually met, degraded responses are *never* written to the
        full-quality cache, and only when the ladder is exhausted does
        admission refuse outright.  Dispatch is wrapped in
        retry-with-backoff; a micro-batch that keeps failing is failed
        *alone* (typed ``failed`` results + fingerprint quarantine) and
        the engine keeps serving.

    A store-backed runtime drains staged mutations between dispatches
    like `MIPSServeEngine`; a failing store flush (`StoreFlushError`)
    leaves the staged ops intact, is counted, and is retried at the next
    poll while serving continues on the current table.

    `stats()` exports p50/p95/p99 latency, queue depth/peak, outcome and
    shed/reject/retry/degraded counters, per-rung eps_served counts and
    per-dispatch lane accounting.  Drive it exactly like the engine:
    ``submit(q, now=...)`` / ``poll(now=...)`` / ``result(rid)`` — every
    request terminates as a typed `ServeResult`; traffic never raises.
    """

    def __init__(self, table, *, K: int = 1, eps: float = 0.1,
                 delta: float = 0.1, eps_floor: Optional[float] = None,
                 degrade_rungs: int = 3, degrade_start: float = 0.5,
                 lanes: int = 8, batch_wait_ms: float = 2.0,
                 queue_capacity: int = 64,
                 classes: Optional[Dict[str, PriorityClass]] = None,
                 default_class: str = "default",
                 max_retries: int = 2, retry_backoff_ms: float = 1.0,
                 dispatch_timeout_ms: Optional[float] = None,
                 fault_injector=None,
                 cache_entries: int = 512, cache_resolution: float = 1e-3,
                 recall_sample_rate: float = 0.0,
                 value_range: Optional[float] = None,
                 qmax_hint: float = 1.0, tile: int = 8, block: int = 512,
                 mesh=None, model_axis: str = "model",
                 n_valid: Optional[int] = None,
                 use_pallas: Optional[bool] = None,
                 precision: str = "fp32", range_slack: float = 1.0,
                 adaptive: bool = False, bound: str = "hoeffding",
                 pull_mode: str = "row", coord_block: int = 128,
                 quant_err: Optional[float] = None,
                 pq_subdims: int = 8, pq_codes: int = 16,
                 seed: int = 0,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer=None, flight=None):
        if batch_wait_ms <= 0:
            raise ValueError(f"batch_wait_ms must be > 0, "
                             f"got {batch_wait_ms}")
        if lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {lanes}")
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        #: optional `repro.obs.trace.SpanTracer` / `repro.obs.flight.
        #: FlightRecorder`; None disables that pillar entirely
        self.tracer = tracer
        self.flight = flight
        self.ladder = DegradationLadder(eps, eps_floor, rungs=degrade_rungs,
                                        start=degrade_start)
        # pull_mode='hybrid' resolves per rung: relaxed-eps rungs have
        # smaller schedules, so the row/coord winner may legitimately
        # differ across the ladder (each rung's plan records its own
        # resolved mode)
        self._rung_execs = [CascadeExecutor(
            table, K=K, eps=e, delta=delta, value_range=value_range,
            qmax_hint=qmax_hint, tile=tile, block=block, lanes=lanes,
            mesh=mesh, model_axis=model_axis, n_valid=n_valid,
            use_pallas=use_pallas, precision=precision,
            range_slack=range_slack, adaptive=adaptive, bound=bound,
            pull_mode=pull_mode, coord_block=coord_block,
            quant_err=quant_err, pq_subdims=pq_subdims, pq_codes=pq_codes,
            metrics=self.metrics, metrics_labels={"rung": str(i)})
            for i, e in enumerate(self.ladder.eps_values)]
        ex0 = self._rung_execs[0]
        self.K = K
        self.lanes = int(lanes)
        self.batch_wait_s = float(batch_wait_ms) * 1e-3
        self._eps, self._delta = float(eps), float(delta)
        self.max_retries = int(max_retries)
        self.retry_backoff_s = float(retry_backoff_ms) * 1e-3
        self.dispatch_timeout_s = (None if dispatch_timeout_ms is None
                                   else float(dispatch_timeout_ms) * 1e-3)
        self.admission = AdmissionController(
            ex0.N, queue_capacity=queue_capacity, classes=classes,
            default_class=default_class, metrics=self.metrics)
        self.injector = fault_injector
        self._store = ex0.store
        if fault_injector is not None:
            self.metrics.adopt(fault_injector.metrics)
        if fault_injector is not None and self._store is not None:
            fault_injector.attach(self._store)
        if self._store is not None:
            self.metrics.adopt(self._store.metrics)
        self._version = 0 if self._store is None else self._store.version
        self._key = jax.random.PRNGKey(seed)
        self.cache = QuantizedLRU(cache_entries, cache_resolution)
        self._results: Dict[int, ServeResult] = {}
        self._next_id = 0
        self._recall_rate = float(recall_sample_rate)
        self._recall_rng = np.random.default_rng(seed)
        self._lat: List[float] = []
        self._occupancy: List[int] = []
        self._pull_fracs: List[float] = []
        self._recalls: List[float] = []
        self._c_requests = self.metrics.counter(
            "serve_requests_total", "Requests submitted, by class.",
            ("priority_class",))
        self._c_outcomes = self.metrics.counter(
            "serve_outcomes_total",
            "Terminal request outcomes (the typed ServeResult statuses).",
            ("outcome",))
        for s in ("ok", "degraded", "rejected", "overloaded", "failed"):
            self._c_outcomes.seed(outcome=s)
        self._c_class = self.metrics.counter(
            "serve_class_events_total",
            "Per-priority-class accounting events.",
            ("priority_class", "event"))
        self._c_rung = self.metrics.counter(
            "serve_rung_served_total",
            "Requests answered per degradation-ladder rung.", ("rung",))
        for i in range(self.ladder.n_rungs):
            self._c_rung.seed(rung=str(i))
        self._c_cache_hits = self.metrics.counter(
            "serve_cache_hits_total", "Requests answered from the LRU.")
        self._c_dispatches = self.metrics.counter(
            "serve_dispatches_total",
            "Batch dispatches, by lane occupancy.", ("filled",))
        self._c_dispatches.seed(filled="full")
        self._c_dispatches.seed(filled="partial")
        self._c_retries = self.metrics.counter(
            "serve_retries_total", "Dispatch retry attempts.")
        self._c_dispatch_errors = self.metrics.counter(
            "serve_dispatch_errors_total",
            "Dispatch attempts that raised (injected or real).")
        self._c_failed_batches = self.metrics.counter(
            "serve_failed_batches_total",
            "Micro-batches failed past the retry budget.")
        self._c_slow = self.metrics.counter(
            "serve_slow_dispatches_total",
            "Dispatches exceeding dispatch_timeout_ms.")
        self._c_flush_failures = self.metrics.counter(
            "serve_store_flush_failures_total",
            "Store flushes failed by StoreFlushError (retried later).")
        self._c_update_errors = self.metrics.counter(
            "serve_update_errors_total",
            "Store flushes that raised a non-flush error.")
        self._c_update_rows = self.metrics.counter(
            "serve_update_rows_total", "Store mutations applied.")
        self._h_latency = self.metrics.histogram(
            "serve_latency_ms",
            "Answered-request latency (ms), by outcome.", ("outcome",))
        self._h_queue_wait = self.metrics.histogram(
            "serve_queue_wait_ms",
            "Submit-to-dispatch queue wait (ms) of dispatched requests.")
        self._h_occupancy = self.metrics.histogram(
            "serve_batch_occupancy", "Filled lanes per dispatch.",
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0))
        self._h_pull_frac = self.metrics.histogram(
            "serve_pull_frac",
            "Executed pull fraction per dispatch (pulls / budget).",
            buckets=PULL_FRAC_BUCKETS)
        self.metrics.gauge(
            "serve_cache_entries", "Live LRU cache entries.",
        ).set_fn(lambda: len(self.cache))
        #: plain dispatch sequence for PRNG fold keys — deliberately NOT
        #: registry-backed so metric wiring (or the NullRegistry hard-off
        #: switch) can never perturb sampling keys
        self._dispatch_seq = 0
        self._seen_refreshes = (getattr(self._store, "codebook_refreshes", 0)
                                if self._store is not None else 0)

    # ---- legacy counter surface (registry-backed) ------------------------

    @property
    def outcomes(self) -> Dict[str, int]:
        """Terminal outcome counts keyed by status (registry-backed)."""
        return {s: int(self._c_outcomes.get(outcome=s))
                for s in ("ok", "degraded", "rejected", "overloaded",
                          "failed")}

    @property
    def rung_served(self) -> List[int]:
        """Requests answered per ladder rung (registry-backed)."""
        return [int(self._c_rung.get(rung=str(i)))
                for i in range(self.ladder.n_rungs)]

    @property
    def per_class(self) -> Dict[str, Dict[str, int]]:
        """Per-class event counts, classes in first-seen order
        (registry-backed)."""
        out: Dict[str, Dict[str, int]] = {}
        for labels, value in self._c_class.rows():
            cls = labels["priority_class"]
            out.setdefault(cls, {})[labels["event"]] = int(value)
        return out

    @property
    def n_requests(self) -> int:
        """Requests submitted (registry-backed)."""
        return int(self._c_requests.total())

    @property
    def n_cache_hits(self) -> int:
        """Cache-answered requests (registry-backed)."""
        return int(self._c_cache_hits.total())

    @property
    def n_dispatches(self) -> int:
        """Batch dispatches issued (registry-backed)."""
        return int(self._c_dispatches.total())

    @property
    def n_full_dispatches(self) -> int:
        """Dispatches with every lane filled (registry-backed)."""
        return int(self._c_dispatches.get(filled="full"))

    @property
    def n_retries(self) -> int:
        """Dispatch retry attempts (registry-backed)."""
        return int(self._c_retries.total())

    @property
    def n_dispatch_errors(self) -> int:
        """Dispatch attempts that raised (registry-backed)."""
        return int(self._c_dispatch_errors.total())

    @property
    def n_failed_batches(self) -> int:
        """Micro-batches failed past retries (registry-backed)."""
        return int(self._c_failed_batches.total())

    @property
    def n_slow_dispatches(self) -> int:
        """Dispatches past the timeout (registry-backed)."""
        return int(self._c_slow.total())

    @property
    def n_flush_failures(self) -> int:
        """StoreFlushError flush failures (registry-backed)."""
        return int(self._c_flush_failures.total())

    @property
    def n_update_errors(self) -> int:
        """Non-flush store update errors (registry-backed)."""
        return int(self._c_update_errors.total())

    @property
    def n_updates(self) -> int:
        """Store mutations applied (registry-backed)."""
        return int(self._c_update_rows.total())

    # ---- compat surface for simulate_stream ------------------------------

    @property
    def N(self) -> int:
        """Query dimensionality (executor-owned)."""
        return self._rung_execs[0].N

    @property
    def n(self) -> int:
        """Row capacity of the served table (executor-owned)."""
        return self._rung_execs[0].n

    @property
    def plan(self):
        """The full-quality (rung 0) executor's calibrated plan."""
        return self._rung_execs[0].plan

    @property
    def executor(self) -> CascadeExecutor:
        """The full-quality (rung 0) executor."""
        return self._rung_execs[0]

    @property
    def deadline_s(self) -> float:
        """Batch-assembly wait in seconds (simulate_stream drain step)."""
        return self.batch_wait_s

    @property
    def pending_count(self) -> int:
        """Requests admitted but not yet dispatched (the queue depth)."""
        return self.admission.depth

    # ---- request path -----------------------------------------------------

    def _class_counter(self, cls: str, key: str) -> None:
        # seed the full event set on a class's first touch so the
        # legacy per-class dict keeps its fixed key order
        for ev in ("requests", "answered", "degraded", "shed"):
            self._c_class.seed(priority_class=cls, event=ev)
        self._c_class.inc(priority_class=cls, event=key)

    def _finish(self, rid: int, res: ServeResult,
                t: Optional[float] = None) -> None:
        self._results[rid] = res
        self._c_outcomes.inc(outcome=res.status)
        if res.answered:
            self._class_counter(res.cls, "answered")
            if res.status == "degraded":
                self._class_counter(res.cls, "degraded")
            self._lat.append(res.latency_s)
            self._h_latency.observe(res.latency_s * 1e3,
                                    outcome=res.status)
            if len(self._lat) > 100_000:
                self._lat = self._lat[-10_000:]
        elif res.status in ("overloaded", "failed"):
            self._class_counter(res.cls, "shed")
        if self.tracer is not None and t is not None:
            self.tracer.request_end(
                rid, t, res.status,
                **({"reason": res.reason} if res.reason else {}))
        if self.flight is not None and res.status == "failed":
            self.flight.record("request_failed", t, rid=rid,
                               cls=res.cls, reason=res.reason)

    def _salted(self, base_key: bytes) -> bytes:
        """Prefix an LRU base key with the live (version, K) salt."""
        return struct.pack("<qi", self._version, self.K) + base_key

    def submit(self, q, now: Optional[float] = None,
               cls: Optional[str] = None) -> int:
        """Accept one query; always returns a request id, never raises.

        The query runs the admission pipeline (DESIGN.md §13): poison
        validation -> quarantine -> cache (full-quality hits answer
        immediately at eps_served = eps) -> bounded priority queue.
        Refused requests get their typed `ServeResult` immediately;
        admitted ones resolve at a later `poll`/`drain`.  ``cls`` names a
        configured `PriorityClass` (None = default).  The call is one
        ``serve.submit`` phase span (`repro.obs.trace.phase`) carrying
        the request's ``rid``.
        """
        with phase("serve.submit", rid=self._next_id):
            return self._admit(q, now, cls)

    def _admit(self, q, now: Optional[float], cls: Optional[str]) -> int:
        now = time.perf_counter() if now is None else now
        rid = self._next_id
        self._next_id += 1
        pcls = self.admission.resolve_class(cls)
        self._c_requests.inc(priority_class=pcls.name)
        self._class_counter(pcls.name, "requests")
        if self.tracer is not None:
            self.tracer.request_begin(rid, now, priority_class=pcls.name)
        self.apply_updates(now)
        arr, reason = self.admission.validate(q)
        if arr is None:
            self.admission.count_poison()
            if self.tracer is not None:
                self.tracer.instant(rid, "rejected", now, reason=reason)
            if self.flight is not None:
                self.flight.record("rejected_poison", now, rid=rid,
                                   reason=reason)
            self._finish(rid, ServeResult(status="rejected", cls=pcls.name,
                                          reason=reason), t=now)
            return rid
        ck = self.cache.key(arr) if self.cache.capacity > 0 else None
        if ck is not None:
            hit = self.cache.get(self._salted(ck))
            if hit is not None:
                ids, scores = hit
                self._c_cache_hits.inc()
                if self.tracer is not None:
                    self.tracer.instant(rid, "cache_hit", now)
                self._finish(rid, ServeResult(
                    status="ok", ids=ids, scores=scores,
                    eps_served=self._eps, delta_served=self._delta,
                    cls=pcls.name, cached=True), t=now)
                return rid
        ticket = Ticket(rid, arr, pcls, now, now + pcls.deadline_s, ck,
                        self.admission.fingerprint(arr))
        verdict, displaced = self.admission.admit(ticket)
        for victim, vres in displaced:
            vres.latency_s = now - victim.t_submit
            if self.tracer is not None:
                self.tracer.instant(victim.req_id, "displaced", now,
                                    by=rid)
            if self.flight is not None:
                self.flight.record("displacement", now,
                                   rid=victim.req_id, by=rid,
                                   cls=victim.cls.name)
            self._finish(victim.req_id, vres, t=now)
        if verdict is not None:
            if self.tracer is not None:
                self.tracer.instant(rid, verdict.status, now,
                                    reason=verdict.reason or "")
            if self.flight is not None:
                self.flight.record("refused", now, rid=rid,
                                   status=verdict.status,
                                   reason=verdict.reason)
            self._finish(rid, verdict, t=now)
        else:
            if self.tracer is not None:
                self.tracer.instant(rid, "admitted", now,
                                    depth=self.admission.depth)
            if self.flight is not None:
                self.flight.record("admitted", now, rid=rid,
                                   cls=pcls.name,
                                   depth=self.admission.depth)
        return rid

    def result(self, req_id: int) -> Optional[ServeResult]:
        """Pop the typed `ServeResult` for a finished request, or None."""
        return self._results.pop(req_id, None)

    def warmup(self) -> float:
        """Compile every rung executor off the serving clock; returns s.

        Dispatches one all-zeros lane buffer through each ladder rung so
        jit compilation happens *before* traffic: on a virtual-clock
        driver an un-warmed runtime charges its first dispatch the whole
        compile time, which expires every queued deadline and reads as a
        (spurious) overload.  Legacy counters and stats are untouched
        (the executor-level ``cascade_*`` metrics do count warmup
        dispatches — by design, so compile cost is visible).  The key is
        folded as a dispatch folds it, so that compiles too.
        """
        t0 = time.perf_counter()
        Qbuf = np.zeros((self.lanes, self.N), np.float32)
        key = jax.random.fold_in(self._key, 0)
        for ex in self._rung_execs:
            ex.dispatch(Qbuf, key)
        return time.perf_counter() - t0

    # ---- updates ----------------------------------------------------------

    def apply_updates(self, now: Optional[float] = None) -> int:
        """Drain staged store mutations fault-tolerantly; returns applied.

        Like `MIPSServeEngine.apply_updates` (version bump invalidates +
        re-salts the LRU, the recall mirror stays live, capacity/value
        range growth recalibrates and recompiles every rung executor),
        with one robustness addition: a `StoreFlushError` from the
        store's fault hook — or any other flush exception — is *counted*
        (``stats()["faults"]["store_flush_failures"]`` /
        ``update_errors``), the staged mutations stay staged, and serving
        continues on the current table; the flush retries at the next
        poll.  ``now`` (optional virtual-clock time) only timestamps the
        flight-recorder events.  No-op without a store.
        """
        from repro.store import StoreFlushError
        store = self._store
        if store is None:
            return 0
        applied = 0
        if store.pending_updates:
            try:
                info = store.flush_updates()
                applied = info["applied"]
                self._c_update_rows.inc(applied)
            except StoreFlushError as e:
                # staged ops intact: keep serving the current table and
                # retry the flush at the next poll
                self._c_flush_failures.inc()
                if self.flight is not None:
                    self.flight.record("store_flush_error", now,
                                       error=str(e),
                                       pending=store.pending_updates)
                    self.flight.dump("store_flush_error", now)
            except Exception as e:
                # a genuinely bad mutation (unknown delete, capacity
                # exhausted): the store dropped the bad op and kept its
                # successors — count it and keep the engine alive
                self._c_update_errors.inc()
                if self.flight is not None:
                    self.flight.record("store_update_error", now,
                                       error=str(e))
        if store.version != self._version:
            self._version = store.version
            self.cache.invalidate()
        rebuilt = 0
        for ex in self._rung_execs:
            rebuilt += ex.sync_store()
        if rebuilt and self.flight is not None:
            self.flight.record("recalibration", now, rebuilds=rebuilt,
                               version=store.version)
        refreshes = getattr(store, "codebook_refreshes", 0)
        if refreshes != self._seen_refreshes:
            self._seen_refreshes = refreshes
            if self.flight is not None:
                self.flight.record("codebook_refresh", now,
                                   refreshes=refreshes,
                                   version=store.version)
        return applied

    # ---- scheduler ---------------------------------------------------------

    def poll(self, now: Optional[float] = None) -> Tuple[List[int], float]:
        """Run the continuous-batching scheduler; returns (ids, busy_s).

        Dispatch triggers: ``lanes`` requests queued (full dispatch), the
        oldest queued request aged past ``batch_wait_ms``, or — the
        continuous-batching rule — the executor already ran this poll
        (work conservation: anything still queued waited through that
        dispatch, so freed lanes are refilled immediately instead of
        re-waiting the batch deadline).  Expired-deadline tickets are
        shed during batch assembly.  ``busy_s`` is virtual compute time
        (measured + injected + retry backoff) for virtual-clock drivers.
        The pass is one ``serve.poll`` phase span.
        """
        with phase("serve.poll"):
            now = time.perf_counter() if now is None else now
            self.apply_updates(now)
            done: List[int] = []
            busy = 0.0
            while self.admission.depth:
                t = now + busy
                oldest = self.admission.oldest_submit()
                full = self.admission.depth >= self.lanes
                aged = (oldest is not None
                        and t - oldest >= self.batch_wait_s)
                if not (full or aged or busy > 0.0):
                    break
                batch, expired = self.admission.take(t, self.lanes)
                for tk, res in expired:
                    if self.flight is not None:
                        self.flight.record("deadline_expired", t,
                                           rid=tk.req_id, cls=tk.cls.name)
                    self._finish(tk.req_id, res, t=t)
                    done.append(tk.req_id)
                if not batch:
                    continue
                served, dt = self._dispatch(batch, t)
                done.extend(served)
                busy += dt
            return done, busy

    def drain(self, now: Optional[float] = None) -> Tuple[List[int], float]:
        """Serve everything queued regardless of triggers or deadlines.

        One ``serve.poll`` phase span, like `poll`.
        """
        with phase("serve.poll"):
            now = time.perf_counter() if now is None else now
            self.apply_updates(now)
            done: List[int] = []
            busy = 0.0
            while self.admission.depth:
                batch, _ = self.admission.take(now + busy, self.lanes,
                                               expire=False)
                if not batch:
                    break
                served, dt = self._dispatch(batch, now + busy)
                done.extend(served)
                busy += dt
            return done, busy

    # ---- dispatch ----------------------------------------------------------

    def _fail_batch(self, batch: List[Ticket], t: float, exc: Exception,
                    retries: int, backoff: float) -> List[int]:
        """Fail ONE micro-batch (typed results + quarantine), engine lives.

        Every ticket gets a ``failed`` `ServeResult` carrying the
        exception text, and its fingerprint is quarantined so identical
        resubmissions are refused at admission instead of re-breaking
        dispatches.  The engine itself is untouched — the next poll
        dispatches the next batch normally.
        """
        self._c_failed_batches.inc()
        reason = f"dispatch failed after {retries} retries: {exc}"
        for tk in batch:
            self.admission.add_quarantine(tk.fingerprint,
                                          "dispatch failure")
            if self.flight is not None:
                self.flight.record("quarantine_add", t + backoff,
                                   rid=tk.req_id,
                                   fingerprint=repr(tk.fingerprint))
            self._finish(tk.req_id, ServeResult(
                status="failed", cls=tk.cls.name, reason=reason,
                latency_s=(t + backoff) - tk.t_submit, retries=retries),
                t=t + backoff)
        if self.flight is not None:
            # one dump per failed batch: the ring now holds the whole
            # failure context (injections, retries, quarantines)
            self.flight.dump("request_failed", t + backoff)
        return [tk.req_id for tk in batch]

    def _dispatch(self, batch: List[Ticket],
                  t: float) -> Tuple[List[int], float]:
        # phase spans: serve.assemble up to the executor call (rung, lane
        # buffer, key), the executor's own spans, then serve.finish
        didx = self._dispatch_seq
        with phase("serve.assemble", didx=didx):
            # rung from overload pressure at assembly, the max of two
            # signals: queue depth (the taken batch counts: it was queue
            # content a moment ago) and *urgency* — the fraction of its
            # deadline budget the most-delayed batch member has already
            # burned.  Depth alone misses overload under tight deadlines
            # (requests expire before the queue builds); urgency alone
            # misses it when deadlines are infinite.  Either saturating
            # climbs the ladder.
            load = (self.admission.depth + len(batch)) \
                / self.admission.queue_capacity
            urgency = 0.0
            for tk in batch:
                budget = tk.t_deadline - tk.t_submit
                if np.isfinite(budget) and budget > 0:
                    urgency = max(urgency, (t - tk.t_submit) / budget)
            rung = self.ladder.rung(max(load, urgency))
            ex = self._rung_execs[rung]
            Qbuf = np.zeros((self.lanes, self.N), np.float32)
            for i, tk in enumerate(batch):
                Qbuf[i] = tk.q
            # fold on the plain dispatch sequence, NOT a registry counter:
            # sampling keys must be invariant to observability wiring
            key = jax.random.fold_in(self._key, didx)
            self._dispatch_seq += 1
            self._c_dispatches.inc(
                filled="full" if len(batch) == self.lanes else "partial")

        def on_error(e, attempt, injected):
            self._c_dispatch_errors.inc()
            if self.flight is not None:
                self.flight.record(
                    "fault_dispatch_error", t, didx=didx,
                    attempt=attempt, injected=injected, error=str(e))

        def on_retry(attempt, backoff):
            self._c_retries.inc()
            if self.tracer is not None:
                for tk in batch:
                    self.tracer.instant(tk.req_id, "retry",
                                        t + backoff, attempt=attempt,
                                        didx=didx)

        try:
            ids, scores, rounds, dt, attempt, backoff, spike = \
                dispatch_with_retries(
                    ex, Qbuf, key, didx=didx, filled=len(batch),
                    injector=self.injector,
                    max_retries=self.max_retries,
                    retry_backoff_s=self.retry_backoff_s,
                    on_error=on_error, on_retry=on_retry)
        except DispatchFailed as df:
            return self._fail_batch(batch, t, df.cause, df.retries,
                                    df.backoff), df.backoff
        with phase("serve.finish", didx=didx):
            if spike > 0.0 and self.flight is not None:
                self.flight.record("fault_latency", t, didx=didx,
                                   spike_ms=spike * 1e3)
            if (self.dispatch_timeout_s is not None
                    and dt > self.dispatch_timeout_s):
                self._c_slow.inc()
            ids = ids[:len(batch)]
            scores = scores[:len(batch)]
            self._occupancy.append(len(batch))
            self._h_occupancy.observe(len(batch))
            from repro.distributed.sharding import dispatch_lane_stats
            lane = dispatch_lane_stats(
                None if rounds is None else rounds[:len(batch)],
                schedule=ex.plan.schedule, lanes=self.lanes,
                filled=len(batch))
            self._pull_fracs.append(lane["executed_pull_frac"])
            self._h_pull_frac.observe(lane["executed_pull_frac"])
            eps_r = self.ladder.eps_values[rung]
            self._c_rung.inc(len(batch), rung=str(rung))
            if self.tracer is not None:
                args = {"didx": didx, "rung": rung, "eps_served": eps_r,
                        "occupancy": len(batch), "retries": attempt,
                        "pull_frac": lane["executed_pull_frac"]}
                if spike > 0.0:
                    args["injected_ms"] = spike * 1e3
                if rounds is not None:
                    args["rounds_used"] = float(
                        np.mean(rounds[:len(batch)]))
                self.tracer.global_span(f"dispatch {didx}", t, t + dt,
                                        **args)
            done = []
            for i, tk in enumerate(batch):
                out_ids = ex.external_ids(ids[i])
                self._h_queue_wait.observe((t - tk.t_submit) * 1e3)
                if self.tracer is not None:
                    self.tracer.span(tk.req_id, "queued", tk.t_submit, t,
                                     didx=didx)
                    self.tracer.span(tk.req_id, "serve", t, t + dt,
                                     rung=rung, eps_served=eps_r,
                                     retries=attempt, didx=didx)
                res = ServeResult(
                    status="ok" if rung == 0 else "degraded",
                    ids=out_ids, scores=scores[i].copy(),
                    eps_served=eps_r, delta_served=self._delta,
                    cls=tk.cls.name, latency_s=(t + dt) - tk.t_submit,
                    retries=attempt)
                self._finish(tk.req_id, res, t=t + dt)
                # only full-quality answers are cacheable: a degraded
                # (eps_served > eps) result must never be replayed to a
                # later query as if it met the contract eps
                if rung == 0 and tk.cache_key is not None:
                    self.cache.put(self._salted(tk.cache_key),
                                   (out_ids, scores[i].copy()))
                if (self._recall_rate > 0.0 and
                        self._recall_rng.random() < self._recall_rate):
                    self._recalls.append(ex.recall_of(tk.q, ids[i]))
                done.append(tk.req_id)
            for buf_name in ("_occupancy", "_pull_fracs", "_recalls"):
                buf = getattr(self, buf_name)
                if len(buf) > 100_000:
                    setattr(self, buf_name, buf[-10_000:])
            return done, dt

    # ---- observability -----------------------------------------------------

    def stats(self) -> dict:
        """Runtime telemetry: tail latency, queue, outcomes, faults.

        ``latency_ms`` (p50/p95/p99) covers *answered* requests (cache
        hits at 0); shed/rejected/failed requests are visible in
        ``outcomes`` and ``admission`` instead.  ``degradation`` reports
        the eps ladder and how many responses each rung served
        (``eps_served`` histogram); ``lanes`` aggregates per-dispatch
        lane accounting (occupancy + executed pull fraction);
        ``faults`` reconciles retries / failed batches / store flush
        failures (+ the injector's own schedule when attached).
        """
        occ = np.asarray(self._occupancy, np.float64)
        answered = self.outcomes["ok"] + self.outcomes["degraded"]
        out = {
            "requests": self.n_requests,
            "completed": self.n_requests - self.admission.depth,
            "pending": self.admission.depth,
            "answered": answered,
            "availability": answered / max(1, self.n_requests),
            "dispatches": self.n_dispatches,
            "full_dispatches": self.n_full_dispatches,
            "cache": {"hits": self.cache.hits,
                      "misses": self.cache.misses,
                      "entries": len(self.cache),
                      "hit_rate": (self.cache.hits
                                   / max(1, self.cache.hits
                                         + self.cache.misses))},
            "latency_ms": summarize_latencies(self._lat),
            "queue": self.admission.stats(),
            "outcomes": dict(self.outcomes),
            "classes": {k: dict(v) for k, v in self.per_class.items()},
            "degradation": {
                "eps": self._eps,
                "eps_floor": self.ladder.eps_floor,
                "rungs": list(self.ladder.eps_values),
                "served_per_rung": list(self.rung_served),
                "degraded": self.outcomes["degraded"],
            },
            "lanes": {
                "lanes": self.lanes,
                "mean_occupancy": float(occ.mean()) if occ.size else 0.0,
                "mean_lane_util": (float(occ.mean()) / self.lanes
                                   if occ.size else 0.0),
                "mean_executed_pull_frac": (
                    float(np.mean(self._pull_fracs))
                    if self._pull_fracs else 1.0),
            },
            "faults": {
                "retries": self.n_retries,
                "dispatch_errors": self.n_dispatch_errors,
                "failed_batches": self.n_failed_batches,
                "slow_dispatches": self.n_slow_dispatches,
                "store_flush_failures": self.n_flush_failures,
                "update_errors": self.n_update_errors,
            },
            "recall": {"samples": len(self._recalls),
                       "mean": (float(np.mean(self._recalls))
                                if self._recalls else float("nan"))},
            "plan": {"rounds": len(self.plan.schedule.rounds),
                     "pull_speedup": self.plan.schedule.speedup},
            "updates": {"applied": self.n_updates,
                        "version": self._version,
                        "recalibrations": sum(
                            ex.n_recalibrations
                            for ex in self._rung_execs)},
        }
        if self.injector is not None:
            out["faults"]["injected"] = self.injector.stats()
        if self._store is not None:
            out["store"] = self._store.stats()
        return out
