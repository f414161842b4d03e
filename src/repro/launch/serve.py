"""Serving CLI: request-loop / runtime driver + LM decode demo.

The paper's feature in production position: `--mips boundedme` replaces the
full unembedding matvec at every decode step with the BoundedME bandit
(per-query (eps, delta) knob, zero preprocessing — the vocab table can be
hot-swapped between requests with no index rebuild).

The serving classes themselves live in `repro.launch.engine`
(`MIPSServeEngine`, `ServeRuntime`, `CascadeExecutor`, `QuantizedLRU`) and
`repro.launch.admission` (priority classes, typed results, degradation
ladder); they are re-exported here for backward compatibility.  This
module owns the *driving*: seeded reproducible arrival traces
(`arrival_trace`), the virtual-clock stream driver (`simulate_stream`),
CLI argument validation, and three entry points:

* ``--loop`` — the PR-2 micro-batching request loop (`MIPSServeEngine`):

      PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
          --smoke --loop --requests 256 --batch 8 --deadline-ms 2

* ``--loop --runtime`` — the continuous-batching async runtime
  (DESIGN.md §13: admission control, priority classes, overload
  shedding via the eps degradation ladder, fault injection):

      PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
          --smoke --loop --runtime --requests 512 --pattern bursty \
          --queue-capacity 32 --eps-floor 0.4 \
          --inject-error-rate 0.05 --inject-latency-rate 0.05

* the original batched prefill + greedy decode demo:

      PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
          --smoke --mips boundedme --eps 0.1 --tokens 32
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.admission import (STATUSES,  # noqa: F401
                                    AdmissionController,
                                    DegradationLadder, PriorityClass,
                                    ServeResult)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.engine import (CascadeExecutor,  # noqa: F401
                                 MIPSServeEngine, QuantizedLRU,
                                 ServeRuntime)
from repro.models.model import init_params
from repro.models.steps import decode_step, prefill_step

__all__ = ["QuantizedLRU", "MIPSServeEngine", "ServeRuntime",
           "CascadeExecutor", "PriorityClass", "ServeResult",
           "arrival_trace", "simulate_stream", "main"]

#: namespace tag so trace streams never alias other default_rng users
_TRACE_ROOT = 0x7AC3


def arrival_trace(n: int, *, interarrival_ms: float = 0.1,
                  pattern: str = "uniform", seed: int = 0,
                  burst_factor: float = 8.0, burst_len: int = 16,
                  tail: float = 1.5) -> np.ndarray:
    """Reproducible (n,) arrival times in seconds for a query stream.

    Patterns (all with mean spacing ``interarrival_ms`` except bursty's
    heavy tail):

      * ``uniform`` — exactly ``i * interarrival_ms`` (deterministic,
        seed-independent; the PR-2 default, byte-identical to the old
        driver);
      * ``poisson`` — i.i.d. exponential gaps (memoryless open-loop
        traffic);
      * ``bursty`` — geometric-length bursts of arrivals spaced
        ``interarrival_ms / burst_factor`` apart, separated by
        Pareto(``tail``) heavy-tailed quiet gaps — the overload pattern
        the admission/degradation stack is tested under.

    The trace is a pure function of ``(seed, pattern, parameters)`` —
    two calls with the same arguments return byte-identical arrays, so
    CI can assert exact shed/degrade counters against it.
    """
    d = float(interarrival_ms) * 1e-3
    if n <= 0:
        return np.zeros(0, np.float64)
    if pattern == "uniform":
        return np.arange(n, dtype=np.float64) * d
    rng = np.random.default_rng(
        np.random.SeedSequence([_TRACE_ROOT, int(seed)]))
    if pattern == "poisson":
        gaps = rng.exponential(d, size=n)
        gaps[0] = 0.0
        return np.cumsum(gaps)
    if pattern == "bursty":
        gaps = np.empty(n, np.float64)
        i = 0
        while i < n:
            blen = min(n - i, max(1, int(rng.geometric(1.0 / burst_len))))
            # quiet gap before the burst, heavy-tailed so occasional
            # lulls let the queue drain (and occasional back-to-back
            # bursts overload it)
            gaps[i] = (0.0 if i == 0
                       else d * burst_len * (0.5 + rng.pareto(tail)))
            gaps[i + 1:i + blen] = d / burst_factor
            i += blen
        return np.cumsum(gaps)
    raise ValueError(f"unknown arrival pattern {pattern!r}; "
                     f"use uniform | poisson | bursty")


def simulate_stream(engine, queries, *, interarrival_ms: float = 0.1,
                    churn=None, pattern: str = "uniform", seed: int = 0,
                    open_loop: bool = False, classes=None, tenants=None,
                    burst_factor: float = 8.0, burst_len: int = 16,
                    trace=None, metrics_out=None, trace_out=None) -> dict:
    """Drive a query stream through an engine/runtime on a virtual clock.

    Arrivals follow a reproducible `arrival_trace` (``pattern`` /
    ``seed``; or pass an explicit ``trace`` array) on a simulated clock
    that only advances by (a) arrival spacing and (b) *measured* compute
    time of each dispatch — batching/deadline/overload dynamics are
    exercised exactly as in wall-clock serving, without sleeps.

    ``open_loop=True`` stamps each submit at its *true* trace arrival
    time even when the engine's virtual clock has already passed it,
    and admits every arrival the clock has overtaken *before* the next
    poll (arrivals keep coming while the server is busy — the load
    model under which queues actually grow, batches fill, and shedding
    fires).  The default closed-ish loop (arrivals wait for the clock,
    one submit per poll) matches the PR-2 driver byte-for-byte on the
    uniform pattern.

    ``churn(engine, i)`` (optional) runs before each arrival — stage
    store mutations there to simulate a live corpus.  ``classes(i)``
    (optional, `ServeRuntime` only) names the priority class of arrival
    ``i``.  ``tenants(i)`` (optional, `repro.launch.tenancy.
    MultiTenantRuntime` only) names the tenant whose table serves
    arrival ``i`` — a multi-tenant trace is just a merged arrival trace
    plus this routing function.  Returns the engine stats dict plus
    ``virtual_s``,
    ``throughput_rps`` and the ``trace`` metadata block (pattern, seed,
    span, offered rate) that makes the run reproducible.

    ``metrics_out`` / ``trace_out`` (optional paths) export the engine's
    observability artifacts after the drain: the metrics registry
    snapshot (Prometheus text for ``.prom``/``.txt``, JSON otherwise)
    and the Chrome trace-event JSON of the span tracer
    (docs/OBSERVABILITY.md).  Paths actually written are echoed in an
    ``artifacts`` block of the returned dict.
    """
    n = len(queries)
    if trace is None:
        trace = arrival_trace(n, interarrival_ms=interarrival_ms,
                              pattern=pattern, seed=seed,
                              burst_factor=burst_factor,
                              burst_len=burst_len)
    trace = np.asarray(trace, np.float64)
    now = 0.0
    i = 0
    while i < n:
        now = max(now, float(trace[i]))
        # admit arrival i — and, open loop, every later arrival already
        # overdue because the clock advanced while the server was busy.
        # Without this the queue can never exceed one request and
        # continuous batching degenerates to singleton dispatches.
        while True:
            if churn is not None:
                churn(engine, i)
            kw = {} if classes is None else {"cls": classes(i)}
            if tenants is not None:
                kw["tenant"] = tenants(i)
            engine.submit(queries[i],
                          now=(float(trace[i]) if open_loop else now), **kw)
            i += 1
            if not (open_loop and i < n and float(trace[i]) <= now):
                break
        _, busy = engine.poll(now=now)
        now += busy
        # batch-wait timer: a real async loop flushes a partial batch
        # after batch_wait even with no new arrival to wake it.  Poll at
        # timer ticks across quiet gaps so a burst tail is not stuck
        # queued (and expiring) until the next burst arrives.
        t_next = float(trace[i]) if i < n else np.inf
        while engine.pending_count and now + engine.deadline_s < t_next:
            now += engine.deadline_s
            _, busy = engine.poll(now=now)
            now += busy
    while engine.pending_count:
        now += engine.deadline_s
        _, busy = engine.poll(now=now)
        now += busy
    span = float(trace[-1]) if n else 0.0
    artifacts = {}
    if metrics_out is not None and getattr(engine, "metrics", None) is not None:
        engine.metrics.write(metrics_out)
        artifacts["metrics"] = str(metrics_out)
    if trace_out is not None and getattr(engine, "tracer", None) is not None:
        engine.tracer.write(trace_out)
        artifacts["trace"] = str(trace_out)
    return {"virtual_s": now,
            "throughput_rps": max(1, n) / max(now, 1e-9),
            "trace": {"pattern": pattern, "seed": int(seed),
                      "interarrival_ms": float(interarrival_ms),
                      "open_loop": bool(open_loop),
                      "span_s": span,
                      "offered_rps": n / max(span, 1e-9) if n else 0.0},
            **({"artifacts": artifacts} if artifacts else {}),
            **engine.stats()}


def _make_churn(store, churn_rate: float, scale: float):
    """The --dynamic mutation closure: upserts/delete+append per arrival."""
    crng = np.random.default_rng(1)

    def churn(eng, i):
        if crng.random() >= churn_rate:
            return
        row = (scale * crng.normal(size=eng.N) / np.sqrt(eng.N)
               ).astype(np.float32)
        live = store.live_ids()
        if crng.random() < 0.7 or live.size == 0:
            tgt = (int(crng.choice(live)) if live.size
                   else store.append(row) or 0)
            store.upsert(tgt, row)
        elif store.free_rows > 0:
            store.delete(int(crng.choice(live)))
            store.append(row)

    return churn


def _run_loop(args) -> None:
    """--loop mode: serve a synthetic query stream against the unembedding.

    With ``--dynamic`` the vocab table is wrapped in a
    `repro.store.DynamicTableStore` (or `ShardedTableStore` under
    ``--shards``) and ``--churn-rate`` of the arrivals additionally stage
    an embedding upsert or a delete+append pair — the live-corpus
    scenario (DESIGN.md §11).  With ``--runtime`` the stream is served by
    the continuous-batching `ServeRuntime` (DESIGN.md §13) under the
    chosen arrival ``--pattern``, optionally with deterministic fault
    injection (``--inject-*``).
    """
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    params = init_params(cfg, jax.random.PRNGKey(0))
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    mesh = None
    if args.shards > 1:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(args.shards)
    block = min(512, cfg.d_model)
    churn = None
    store = None
    n_valid = cfg.vocab
    if args.dynamic:
        from repro.store import DynamicTableStore, ShardedTableStore
        table = np.asarray(table, np.float32)[:cfg.vocab]
        if mesh is not None:
            store = ShardedTableStore(
                table, mesh=mesh, block=block,
                capacity_slack=args.capacity_slack)
        else:
            store = DynamicTableStore(
                table, block=block, capacity_slack=args.capacity_slack,
                precision=args.precision, pq_subdims=args.pq_subdims)
        table, n_valid = store, None
        if args.churn_rate > 0:
            churn = _make_churn(store, args.churn_rate,
                                float(store.value_abs_max))

    common = dict(K=args.topk, eps=args.eps, delta=args.delta,
                  mesh=mesh, recall_sample_rate=args.recall_rate,
                  cache_entries=args.cache_entries,
                  precision=args.precision, adaptive=args.adaptive,
                  bound=args.bound, pull_mode=args.pull_mode,
                  pq_subdims=args.pq_subdims)
    if not args.dynamic:
        common.update(block=block, n_valid=n_valid)

    tracer = None
    flight = None
    if args.runtime:
        if args.trace_out:
            from repro.obs import SpanTracer
            tracer = SpanTracer(seed=args.stream_seed)
        if args.flight_recorder_path:
            from repro.obs import FlightRecorder
            flight = FlightRecorder(capacity=args.flight_capacity,
                                    path=args.flight_recorder_path)
        injector = None
        if (args.inject_latency_rate > 0 or args.inject_error_rate > 0
                or args.inject_flush_rate > 0):
            from repro.launch.faults import FaultInjector
            injector = FaultInjector(
                args.fault_seed,
                latency_rate=args.inject_latency_rate,
                error_rate=args.inject_error_rate,
                flush_failure_rate=args.inject_flush_rate)
        classes = {
            "interactive": PriorityClass(
                "interactive", priority=0,
                deadline_ms=args.request_deadline_ms, sheddable=False),
            "default": PriorityClass(
                "default", priority=1,
                deadline_ms=args.request_deadline_ms),
            "batch": PriorityClass(
                "batch", priority=2,
                deadline_ms=4 * args.request_deadline_ms),
        }
        engine = ServeRuntime(
            table, eps_floor=args.eps_floor,
            degrade_rungs=args.degrade_rungs, lanes=args.batch,
            batch_wait_ms=args.deadline_ms,
            queue_capacity=args.queue_capacity, classes=classes,
            max_retries=args.max_retries, fault_injector=injector,
            tracer=tracer, flight=flight, **common)
        # compile every rung before traffic: a compile error raises here
        # instead of being retried into `failed` outcomes
        engine.warmup()
        print(f"[serve] runtime: table=({engine.n},{engine.N}) "
              f"K={args.topk} eps={args.eps} "
              f"eps_floor={engine.ladder.eps_floor} "
              f"rungs={engine.ladder.n_rungs} lanes={args.batch} "
              f"queue={args.queue_capacity} "
              f"pattern={args.pattern} "
              f"shards={mesh.shape['model'] if mesh else 1} "
              f"dynamic={bool(args.dynamic)} churn={args.churn_rate} "
              f"pull_mode={args.pull_mode} "
              f"faults={'on' if injector else 'off'}")
    else:
        engine = MIPSServeEngine(
            table, batch_size=args.batch, deadline_ms=args.deadline_ms,
            **common)
        print(f"[serve] loop: table=({engine.n},{engine.N}) "
              f"K={args.topk} eps={args.eps} batch={args.batch} "
              f"deadline={args.deadline_ms}ms "
              f"shards={mesh.shape['model'] if mesh else 1} "
              f"dynamic={bool(args.dynamic)} churn={args.churn_rate} "
              f"rounds={len(engine.plan.schedule.rounds)} "
              f"precision={engine.plan.precision} "
              f"adaptive={args.adaptive} bound={args.bound} "
              f"pull_mode={engine.plan.pull_mode} "
              f"block={engine.plan.block} "
              f"eps_eff={engine.plan.eps_effective:.4f} "
              f"pull_speedup={engine.plan.schedule.speedup:.2f}x")
    rng = np.random.default_rng(0)
    qs = rng.normal(size=(args.requests, engine.N)).astype(np.float32)
    if args.repeat_rate > 0:                  # cacheable duplicate queries
        n_dup = int(args.requests * args.repeat_rate)
        idx = rng.integers(0, max(1, args.requests - n_dup), n_dup)
        qs[args.requests - n_dup:] = qs[idx]
    cls_fn = None
    if args.runtime:
        crng = np.random.default_rng(args.stream_seed + 1)
        names = ("interactive", "default", "default", "batch")
        picks = crng.integers(0, len(names), args.requests)
        cls_fn = lambda i: names[picks[i]]   # noqa: E731
    stats = simulate_stream(
        engine, qs, interarrival_ms=args.interarrival_ms, churn=churn,
        pattern=args.pattern, seed=args.stream_seed,
        open_loop=args.runtime, classes=cls_fn,
        metrics_out=args.metrics_out, trace_out=args.trace_out)
    if flight is not None:
        # always leave a final snapshot on disk so CI can validate the
        # artifact even on a fault-free run (failure dumps, if any,
        # already happened mid-stream and this one supersedes them)
        dumped = flight.dump("end_of_run", stats["virtual_s"])
        if dumped:
            stats.setdefault("artifacts", {})["flight"] = dumped
    print(json.dumps(stats, indent=2))
    if args.runtime and args.check_outcomes:
        _check_outcomes(args, stats)


def _load_tenant_spec(path: str) -> dict:
    """Parse a ``--tenants`` spec file into {name: spec-dict}.

    The file is JSON: either a mapping of tenant name -> spec, or
    ``{"tenants": {...}}``.  Each spec holds driver keys — ``rows``
    (synthetic table rows, required) and ``rate_factor`` (arrival-rate
    multiplier vs ``--interarrival-ms``, default 1.0; the hot-tenant
    skew knob) — plus any `repro.launch.tenancy.TenantConfig` field
    (``eps``, ``precision``, ``weight``, ``pinned``, ...).  Unknown
    keys are rejected so a typo'd knob cannot silently serve defaults.
    """
    with open(path) as f:
        spec = json.load(f)
    if isinstance(spec, dict) and isinstance(spec.get("tenants"), dict):
        spec = spec["tenants"]
    if not isinstance(spec, dict) or not spec:
        raise ValueError(f"{path}: expected a non-empty JSON object of "
                         f"tenant name -> spec")
    from repro.launch.tenancy import TenantConfig
    cfg_fields = {f.name for f in dataclasses.fields(TenantConfig)}
    driver_keys = {"rows", "rate_factor"}
    for name, s in spec.items():
        if not isinstance(s, dict) or "rows" not in s:
            raise ValueError(f"{path}: tenant {name!r} needs at least "
                             f"{{\"rows\": <n>}}")
        unknown = set(s) - cfg_fields - driver_keys
        if unknown:
            raise ValueError(f"{path}: tenant {name!r} has unknown keys "
                             f"{sorted(unknown)}")
        if float(s.get("rate_factor", 1.0)) <= 0:
            raise ValueError(f"{path}: tenant {name!r} rate_factor must "
                             f"be > 0")
    return spec


def _run_tenants(args) -> None:
    """--tenants mode: one multi-tenant runtime, one merged stream.

    Builds a synthetic table per tenant from the spec (seeded per
    tenant, dim = the arch's d_model), registers all of them in a
    `repro.launch.tenancy.TableRegistry` under ``--table-budget-mb``,
    and drives one merged open-loop arrival trace — each tenant
    arrives at ``rate_factor`` times the base ``--interarrival-ms``
    rate under its own ``--pattern`` stream — through a
    `MultiTenantRuntime` (deficit-round-robin fairness, per-tenant
    admission, LRU residency).  Stats keep the single-runtime top-level
    shape, so ``--check-outcomes`` gates the run unchanged; per-tenant
    and registry breakdowns ride in ``tenants`` / ``registry``.
    """
    from repro.launch.tenancy import (MultiTenantRuntime, TableRegistry,
                                      TenantConfig)
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    dim = cfg.d_model
    spec = _load_tenant_spec(args.tenants)
    tracer = None
    flight = None
    if args.trace_out:
        from repro.obs import SpanTracer
        tracer = SpanTracer(seed=args.stream_seed)
    if args.flight_recorder_path:
        from repro.obs import FlightRecorder
        flight = FlightRecorder(capacity=args.flight_capacity,
                                path=args.flight_recorder_path)
    injector = None
    if (args.inject_latency_rate > 0 or args.inject_error_rate > 0
            or args.inject_flush_rate > 0):
        from repro.launch.faults import FaultInjector
        injector = FaultInjector(
            args.fault_seed,
            latency_rate=args.inject_latency_rate,
            error_rate=args.inject_error_rate,
            flush_failure_rate=args.inject_flush_rate)
    budget = (None if args.table_budget_mb is None
              else int(args.table_budget_mb * 2**20))
    registry = TableRegistry(byte_budget=budget, lanes=args.batch,
                             flight=flight)
    rates = {}
    for idx, (name, s) in enumerate(sorted(spec.items())):
        s = dict(s)
        rows = int(s.pop("rows"))
        rates[name] = float(s.pop("rate_factor", 1.0))
        defaults = dict(K=args.topk, eps=args.eps, delta=args.delta,
                        eps_floor=args.eps_floor,
                        degrade_rungs=args.degrade_rungs,
                        precision=args.precision,
                        pull_mode=args.pull_mode,
                        pq_subdims=args.pq_subdims,
                        adaptive=args.adaptive, bound=args.bound,
                        cache_entries=args.cache_entries,
                        deadline_ms=args.request_deadline_ms,
                        queue_capacity=args.queue_capacity,
                        seed=args.stream_seed + idx)
        defaults.update(s)
        tcfg = TenantConfig(**defaults)
        trng = np.random.default_rng(
            np.random.SeedSequence([_TRACE_ROOT, args.stream_seed, idx]))
        table = (trng.normal(size=(rows, dim)) / np.sqrt(dim)
                 ).astype(np.float32)
        registry.register(name, table, tcfg)
    engine = MultiTenantRuntime(
        registry, batch_wait_ms=args.deadline_ms,
        max_retries=args.max_retries, fault_injector=injector,
        recall_sample_rate=args.recall_rate, seed=args.stream_seed,
        tracer=tracer, flight=flight)
    names = sorted(spec)
    print(f"[serve] tenants: {len(names)} tables dim={dim} "
          f"budget={'none' if budget is None else f'{budget}B'} "
          f"lanes={args.batch} pattern={args.pattern} "
          f"rates={ {n: rates[n] for n in names} } "
          f"faults={'on' if injector else 'off'}")
    engine.warmup()
    # merged arrival trace: each tenant gets its own seeded stream at
    # rate_factor x the base rate; the merge is sorted by arrival time
    total_rate = sum(rates.values())
    per_tenant_n = {n: max(1, int(round(args.requests * rates[n]
                                        / total_rate)))
                    for n in names}
    times, labels = [], []
    for idx, name in enumerate(names):
        tr = arrival_trace(per_tenant_n[name],
                           interarrival_ms=(args.interarrival_ms
                                            / rates[name]),
                           pattern=args.pattern,
                           seed=args.stream_seed + 1000 * (idx + 1),
                           burst_factor=8.0, burst_len=16)
        times.append(tr)
        labels.extend([name] * len(tr))
    times = np.concatenate(times) if times else np.zeros(0)
    order = np.argsort(times, kind="stable")
    trace = times[order]
    labels = [labels[int(j)] for j in order]
    qrng = np.random.default_rng(args.stream_seed)
    qs = qrng.normal(size=(len(trace), dim)).astype(np.float32)
    if args.repeat_rate > 0:
        n_dup = int(len(trace) * args.repeat_rate)
        if n_dup:
            idxs = qrng.integers(0, max(1, len(trace) - n_dup), n_dup)
            qs[len(trace) - n_dup:] = qs[idxs]
    stats = simulate_stream(
        engine, qs, interarrival_ms=args.interarrival_ms,
        pattern=args.pattern, seed=args.stream_seed, open_loop=True,
        tenants=lambda i: labels[i], trace=trace,
        metrics_out=args.metrics_out, trace_out=args.trace_out)
    if flight is not None:
        dumped = flight.dump("end_of_run", stats["virtual_s"])
        if dumped:
            stats.setdefault("artifacts", {})["flight"] = dumped
    print(json.dumps(stats, indent=2))
    if args.check_outcomes:
        _check_outcomes(args, stats)


def _check_outcomes(args, stats: dict) -> None:
    """--check-outcomes: fail the process unless the runtime held its
    serving contract over the stream — reaching this line at all proves
    no exception escaped `simulate_stream`; on top of that every request
    must have finished with exactly one typed status from the closed
    set, and the answered tail latency must stay inside 8x the request
    deadline (expiry bounds queueing; dispatch + retries ride on top).
    Without fault injection no request may end ``failed``: a failure
    there is a broken dispatch, not a contract-conforming refusal.
    Used by the CI overload + fault-injection smoke job."""
    o = stats["outcomes"]
    unknown = set(o) - set(STATUSES)
    if unknown:
        sys.exit(f"[check] unknown outcome statuses: {sorted(unknown)}")
    injected = (args.inject_latency_rate > 0 or args.inject_error_rate > 0
                or args.inject_flush_rate > 0)
    if o.get("failed", 0) and not injected:
        sys.exit(f"[check] {o['failed']} requests failed with no fault "
                 f"injection asked for")
    total = sum(o.values())
    if total != stats["requests"]:
        sys.exit(f"[check] {stats['requests']} requests but {total} "
                 f"typed outcomes — a request finished without a "
                 f"status, or with two")
    bound = 8.0 * args.request_deadline_ms
    p99 = stats["latency_ms"]["p99"]
    if stats["completed"] and p99 > bound:
        sys.exit(f"[check] p99 {p99:.1f}ms exceeds {bound:.0f}ms "
                 f"(8x --request-deadline-ms)")
    print(f"[check] OK: outcomes closed, {stats['requests']} requests "
          f"all typed, p99 {p99:.1f}ms <= {bound:.0f}ms")


def _run_decode_demo(args) -> None:
    """Default mode: batched prefill + greedy decode with MIPS logits."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    cfg = dataclasses.replace(cfg, mips_mode=args.mips, mips_eps=args.eps,
                              mips_delta=args.delta,
                              mips_precision=args.precision)

    if cfg.mips_mode == "boundedme":
        # the decode hot path runs the whole bandit as ONE fused kernel
        # dispatch per batch (DESIGN.md §3); surface the static plan so the
        # (eps, delta) <-> pull-count trade is visible at launch
        from repro.core.schedule import flatten_schedule
        from repro.kernels.ops import on_tpu
        from repro.models.steps import make_mips_plan
        plan = make_mips_plan(cfg, K=1)
        flat = flatten_schedule(plan.schedule, final_coverage=True)
        path = ("fused pallas_call, dispatches_per_decode_batch=1"
                if on_tpu() else "jnp scan fallback (non-TPU backend)")
        print(f"[serve] fused cascade: rounds={len(plan.schedule.rounds)} "
              f"dense_round1_pulls={flat.dense_pulls} "
              f"steps_per_query={flat.n_steps} "
              f"precision={plan.precision} "
              f"pull_speedup={plan.schedule.speedup:.2f}x path={path}")

    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, P = args.batch, args.prompt_len
    cache_len = P + args.tokens
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (B, P)), jnp.int32)
    kw = {}
    if cfg.family == "vlm":
        kw["patch_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_patches, cfg.d_model)), jnp.float32)
    if cfg.family == "encdec":
        kw["enc_frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.encoder_seq, cfg.d_model)), jnp.float32)

    t0 = time.time()
    _, caches = prefill_step(params, cfg, prompt, cache_len=cache_len, **kw)
    jax.block_until_ready(caches)
    t_prefill = time.time() - t0

    dfn = jax.jit(lambda p, c, t, pos, k: decode_step(p, cfg, c, t, pos,
                                                      key=k))
    tok = prompt[:, -1:]
    out = []
    t0 = time.time()
    for i in range(args.tokens):
        pos = jnp.int32(P + i)
        nxt, caches = dfn(params, caches, tok, pos,
                          jax.random.PRNGKey(i))
        out.append(np.asarray(nxt))
        tok = nxt[:, None]
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = np.stack(out, axis=1)
    print(f"[serve] arch={cfg.name} mips={cfg.mips_mode} "
          f"eps={cfg.mips_eps} batch={B}")
    print(f"[serve] prefill {P} toks: {t_prefill*1e3:.1f} ms; "
          f"decode {args.tokens} toks: {t_decode*1e3:.1f} ms "
          f"({t_decode/args.tokens*1e3:.2f} ms/tok)")
    print(f"[serve] first sequences: {gen[0][:16].tolist()}")


def _validate_args(ap: argparse.ArgumentParser, args) -> None:
    """Fail fast on inconsistent CLI combinations, with actionable errors.

    Every check here would otherwise surface minutes later as a confusing
    deep failure (a churn closure that never fires, a ladder that refuses
    to build, a zero batch deadline that flushes every poll) — so the CLI
    refuses up front and says what to change.
    """
    if args.churn_rate > 0 and not args.dynamic:
        ap.error(f"--churn-rate {args.churn_rate} requires --dynamic: "
                 f"churn mutates a DynamicTableStore, but without "
                 f"--dynamic the table is a static array (add --dynamic, "
                 f"or drop --churn-rate)")
    if not 0.0 <= args.churn_rate <= 1.0:
        ap.error(f"--churn-rate must be in [0, 1], got {args.churn_rate}")
    if args.deadline_ms <= 0:
        ap.error(f"--deadline-ms must be > 0, got {args.deadline_ms}: it "
                 f"is the batch-assembly wait; 0 would flush a "
                 f"single-request batch at every poll (for per-request "
                 f"completion deadlines use --request-deadline-ms)")
    if args.eps_floor is not None:
        if not (args.runtime or args.tenants):
            ap.error("--eps-floor requires --runtime or --tenants: the "
                     "degradation ladder lives in the continuous-"
                     "batching runtimes (add --runtime, or drop "
                     "--eps-floor)")
        if args.eps_floor < args.eps:
            ap.error(f"--eps-floor {args.eps_floor} must be >= --eps "
                     f"{args.eps}: overload *relaxes* eps toward the "
                     f"floor (a floor tighter than the contract would "
                     f"mean degrading improves accuracy)")
    for name, val in (("--inject-latency-rate", args.inject_latency_rate),
                      ("--inject-error-rate", args.inject_error_rate),
                      ("--inject-flush-rate", args.inject_flush_rate)):
        if not 0.0 <= val <= 1.0:
            ap.error(f"{name} must be in [0, 1], got {val}")
        if val > 0 and not (args.runtime or args.tenants):
            ap.error(f"{name} requires --runtime or --tenants: fault "
                     f"injection is wired through the runtimes' "
                     f"retry/quarantine machinery (add --runtime)")
    if args.inject_flush_rate > 0 and not (args.dynamic or args.tenants):
        ap.error("--inject-flush-rate requires --dynamic or --tenants: "
                 "flush faults fire inside a store's flush_updates, and "
                 "without either there is no store")
    if args.tenants is not None:
        if not args.loop:
            ap.error("--tenants requires --loop: the multi-tenant "
                     "registry serves the request stream, not the "
                     "decode demo")
        if args.runtime:
            ap.error("--tenants is its own runtime mode; drop --runtime "
                     "(the MultiTenantRuntime is always continuous-"
                     "batching)")
        if args.dynamic or args.shards > 1:
            ap.error("--tenants builds its own stores per tenant; drop "
                     "--dynamic/--shards (per-tenant precision and "
                     "placement live in the spec file)")
    if args.table_budget_mb is not None:
        if args.tenants is None:
            ap.error("--table-budget-mb requires --tenants: the byte "
                     "budget governs the multi-tenant table registry")
        if args.table_budget_mb <= 0:
            ap.error(f"--table-budget-mb must be > 0, got "
                     f"{args.table_budget_mb}")
    if args.queue_capacity < 1:
        ap.error(f"--queue-capacity must be >= 1, "
                 f"got {args.queue_capacity}")
    if args.request_deadline_ms <= 0:
        ap.error(f"--request-deadline-ms must be > 0, got "
                 f"{args.request_deadline_ms} (per-request completion "
                 f"budget; requests older than it are shed)")
    if args.batch < 1:
        ap.error(f"--batch must be >= 1, got {args.batch}")
    if not 0.0 <= args.repeat_rate <= 1.0:
        ap.error(f"--repeat-rate must be in [0, 1], got {args.repeat_rate}")
    if (args.pull_mode != "row" and args.dynamic
            and args.precision != "fp32" and args.shards <= 1):
        ap.error(f"--pull-mode {args.pull_mode} is incompatible with a "
                 f"single-device quantized store (--dynamic --precision "
                 f"{args.precision}): the store's incrementally maintained "
                 f"{args.precision} shadow fixes the quantization-block "
                 f"geometry, which only the 'row' plan matches (use "
                 f"--pull-mode row, fp32, or --shards 2+)")
    if args.trace_out and not (args.runtime or args.tenants):
        ap.error("--trace-out requires --runtime or --tenants: span "
                 "tracing hooks live in the continuous-batching "
                 "runtimes")
    if args.flight_recorder_path and not (args.runtime or args.tenants):
        ap.error("--flight-recorder-path requires --runtime or "
                 "--tenants: the flight recorder records runtime "
                 "lifecycle events")
    if args.flight_capacity < 1:
        ap.error(f"--flight-capacity must be >= 1, "
                 f"got {args.flight_capacity}")
    if args.metrics_out and not (args.loop or args.runtime):
        ap.error("--metrics-out requires --loop or --runtime: the "
                 "decode demo does not run a metrics-instrumented "
                 "serving engine")
    if args.pq_subdims < 1:
        ap.error(f"--pq-subdims must be >= 1, got {args.pq_subdims}")
    if args.precision == "pq" and not (args.loop or args.runtime):
        ap.error("--precision pq requires --loop or --runtime: pq plans "
                 "need a measured quantization-error bound calibrated on "
                 "the served table (DESIGN.md §10), which the serving "
                 "engines perform at build time; the decode demo's "
                 "trace-time plan has no table to calibrate on")


def _build_parser() -> argparse.ArgumentParser:
    """The serve CLI parser (separate from `main` so tests can drive
    `_validate_args` against real parsed argv)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mips", default="exact",
                    choices=["exact", "boundedme"])
    ap.add_argument("--eps", type=float, default=0.1)
    ap.add_argument("--delta", type=float, default=0.1)
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "int8", "int4", "pq"],
                    help="sampling arithmetic of the cascade: int8/int4 "
                         "quantized pulls under widened bounds (int4 "
                         "nibble-packed, half the bytes), pq per-subspace "
                         "codebook pulls under a measured error bound "
                         "(DESIGN.md §10)")
    ap.add_argument("--pq-subdims", type=int, default=8,
                    help="product-quantization subspace width "
                         "(--precision pq; must divide the block width)")
    ap.add_argument("--adaptive", action="store_true",
                    help="certify per-query early exit at round "
                         "boundaries (DESIGN.md §12); easy queries stop "
                         "pulling inside the same (eps, delta) contract")
    ap.add_argument("--bound", default="hoeffding",
                    choices=["hoeffding", "bernstein"],
                    help="certification radius family for --adaptive "
                         "(bernstein = variance-aware, more pulls/round)")
    ap.add_argument("--pull-mode", default="row",
                    choices=["row", "coord", "hybrid"],
                    help="reward stream of the cascade (DESIGN.md §14): "
                         "'row' samples wide feature blocks, 'coord' the "
                         "BanditMIPS coordinate estimator (pull cost "
                         "sublinear in d), 'hybrid' prices both plans and "
                         "serves the cheaper")
    ap.add_argument("--batch", type=int, default=4,
                    help="micro-batch size (--loop) / kernel lanes "
                         "(--runtime) / decode batch (demo)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    # request-loop mode
    ap.add_argument("--loop", action="store_true",
                    help="run the micro-batching MIPS request loop")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--deadline-ms", type=float, default=2.0,
                    help="batch-assembly wait (micro-batch deadline)")
    ap.add_argument("--interarrival-ms", type=float, default=0.1)
    ap.add_argument("--topk", type=int, default=4)
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--cache-entries", type=int, default=512)
    ap.add_argument("--repeat-rate", type=float, default=0.1,
                    help="fraction of requests repeating an earlier query")
    ap.add_argument("--recall-rate", type=float, default=0.05)
    ap.add_argument("--dynamic", action="store_true",
                    help="serve from a mutable DynamicTableStore "
                         "(zero-rebuild upserts/deletes, DESIGN.md §11)")
    ap.add_argument("--churn-rate", type=float, default=0.0,
                    help="fraction of arrivals that also mutate the "
                         "table (needs --dynamic)")
    ap.add_argument("--capacity-slack", type=float, default=1.5,
                    help="store capacity headroom factor (--dynamic)")
    # continuous-batching runtime mode (DESIGN.md §13)
    ap.add_argument("--runtime", action="store_true",
                    help="serve with the continuous-batching async "
                         "runtime (admission control, priority classes, "
                         "eps degradation ladder, typed refusals)")
    ap.add_argument("--queue-capacity", type=int, default=64,
                    help="bounded admission queue depth (--runtime)")
    ap.add_argument("--eps-floor", type=float, default=None,
                    help="worst eps the degradation ladder may serve "
                         "under overload (>= --eps; default: no "
                         "degradation)")
    ap.add_argument("--degrade-rungs", type=int, default=3,
                    help="precompiled eps rungs between --eps and "
                         "--eps-floor")
    ap.add_argument("--request-deadline-ms", type=float, default=50.0,
                    help="per-request completion budget (--runtime); "
                         "requests queued past it are shed, not served "
                         "late")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="dispatch retry budget before a micro-batch is "
                         "failed (--runtime)")
    ap.add_argument("--pattern", default="uniform",
                    choices=["uniform", "poisson", "bursty"],
                    help="arrival pattern of the simulated stream")
    ap.add_argument("--stream-seed", type=int, default=0,
                    help="seed of the reproducible arrival trace")
    ap.add_argument("--inject-latency-rate", type=float, default=0.0,
                    help="fault injection: per-dispatch latency-spike "
                         "probability (--runtime)")
    ap.add_argument("--inject-error-rate", type=float, default=0.0,
                    help="fault injection: per-dispatch exception "
                         "probability (--runtime)")
    ap.add_argument("--inject-flush-rate", type=float, default=0.0,
                    help="fault injection: store flush failure "
                         "probability (--runtime --dynamic)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the deterministic fault schedule")
    ap.add_argument("--check-outcomes", action="store_true",
                    help="after the stream, fail unless every request "
                         "got a typed status from the closed set and "
                         "p99 stayed inside 8x the request deadline "
                         "(CI smoke contract; --runtime)")
    # observability artifacts (docs/OBSERVABILITY.md)
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics-registry snapshot here after "
                         "the stream (.prom/.txt = Prometheus text "
                         "exposition, anything else = JSON)")
    ap.add_argument("--trace-out", default=None,
                    help="write per-request span traces here as Chrome "
                         "trace-event JSON — load in Perfetto / "
                         "chrome://tracing (--runtime)")
    ap.add_argument("--flight-recorder-path", default=None,
                    help="arm the crash flight recorder: a bounded ring "
                         "of structured serving events dumped here on "
                         "request failure / store-flush error, plus a "
                         "final end-of-run snapshot (--runtime)")
    ap.add_argument("--flight-capacity", type=int, default=256,
                    help="flight-recorder ring size in events")
    # multi-tenant mode (DESIGN.md §16)
    ap.add_argument("--tenants", default=None, metavar="SPEC.json",
                    help="serve a multi-tenant registry instead of one "
                         "table: JSON mapping tenant name -> spec "
                         "({'rows': n, 'rate_factor': r, plus any "
                         "TenantConfig field}); drives one merged "
                         "arrival trace through the deficit-round-robin "
                         "MultiTenantRuntime (--loop)")
    ap.add_argument("--table-budget-mb", type=float, default=None,
                    help="device-memory budget for resident tenant "
                         "tables (MB); cold tables are paged out LRU "
                         "(--tenants; default: unbounded)")
    return ap


def main():
    """CLI: `--loop` for the request loop, default for the decode demo."""
    ap = _build_parser()
    args = ap.parse_args()
    _validate_args(ap, args)
    enable_compile_cache()
    if args.tenants is not None:
        _run_tenants(args)
    elif args.loop:
        _run_loop(args)
    else:
        _run_decode_demo(args)


if __name__ == "__main__":
    main()
