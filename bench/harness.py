"""One benchmark run of one cell: set-up, measured window, check, result line.

The cell, its configuration and its traffic mix are data: the cell's entry
in ``BENCHMARK.json`` names a configuration (whose ``file`` lies under
``bench/configs/``) and a traffic mix (``bench/traffic/<traffic>.json``);
each per-layer metric is read by ``bench/metrics/<name>.py``.  This file
needs no edit for a new cell, configuration, mix or metric.

The system under test is ``repro.launch.engine.ServeRuntime``, driven
through ``submit`` / ``poll`` / ``result`` with ``now`` from
``time.perf_counter()``, the way a client process would.  A closed loop
keeps ``clients`` requests outstanding, each client sending its next
query as soon as its answer is back; an open loop sends each query at its
due time from `bench.loadgen.open_loop_schedule` whatever the backlog.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

from bench import checks, loadgen, reference, roofline, xplane

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoDevice(RuntimeError):
    """The chips the cell needs are not there."""


def load_spec(workload: str, root: Path = ROOT) -> dict:
    """The cell's manifest entry, configuration, mix and per-layer metrics."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if workload in m.get("workloads", [workload])
             and m["moves"] in reported]
    return {"cell": cell, "config": cfg, "mix": mix, "end_to_end": e2e,
            "per_layer": layer}


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices_for(chips: int, require_tpu: bool):
    """The devices a run uses; raises `NoDevice` if they are missing."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's backend is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def build_runtime(table, cfg: dict, *, mesh, seed: int, capacity: int):
    """A `ServeRuntime` as the configuration states it."""
    from repro.launch.admission import PriorityClass
    from repro.launch.engine import ServeRuntime

    classes = {"default": PriorityClass("default", priority=1,
                                        deadline_ms=0.0)}
    return ServeRuntime(
        table, K=cfg["K"], eps=cfg["eps"], delta=cfg["delta"],
        eps_floor=cfg["eps"], lanes=cfg["lanes"],
        batch_wait_ms=cfg["batch_wait_ms"], queue_capacity=capacity,
        classes=classes, cache_entries=0, recall_sample_rate=0.0,
        value_range=loadgen.value_range(cfg), qmax_hint=cfg["qmax_hint"],
        tile=cfg["tile"], block=cfg["block"], mesh=mesh,
        n_valid=cfg["n_valid"], precision=cfg["precision"],
        pull_mode=cfg["pull_mode"],
        seed=loadgen.seed_words(seed, loadgen.RUNTIME))


class _Window:
    """What the measured window saw: sends, answers and their times."""

    def __init__(self):
        self.sent = []          # (pool index, due or send time)
        self.answers = {}       # send slot -> (ids, scores, t_hold)
        self.late = []          # open loop: submit time - due time


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


def closed_loop(rt, Q: np.ndarray, clients: int, seconds: float) -> tuple:
    """``clients`` closed-loop clients for ``seconds``; returns the record."""
    w = _Window()
    inflight = {}               # rid -> slot
    t0 = time.perf_counter()
    stop = t0 + seconds

    def send(now):
        slot = len(w.sent)
        w.sent.append((slot % len(Q), now))
        rid = rt.submit(Q[slot % len(Q)], now=now)
        if rt.result(rid) is None:      # else refused at the door: missing
            inflight[rid] = slot

    with _annotate("submit"):
        for _ in range(clients):
            send(time.perf_counter())
    while inflight:
        with _annotate("poll"):
            done, _ = rt.poll(now=time.perf_counter())
        t_hold = time.perf_counter()
        with _annotate("result"):
            for rid in done:
                slot = inflight.pop(rid)
                res = rt.result(rid)
                if res is not None and res.answered:
                    w.answers[slot] = (res.ids, res.scores, t_hold)
        if t_hold < stop:
            with _annotate("submit"):
                for _ in done:
                    send(time.perf_counter())
    return w, t0


def open_loop(rt, Q: np.ndarray, due: np.ndarray, wait_s: float) -> tuple:
    """Sends query i at ``t0 + due[i]`` whatever the backlog."""
    w = _Window()
    inflight = {}
    i = 0
    t0 = time.perf_counter()
    while i < len(due) or inflight:
        now = time.perf_counter()
        if i < len(due) and t0 + due[i] <= now:
            with _annotate("submit"):
                while i < len(due) and t0 + due[i] <= now:
                    slot = len(w.sent)
                    w.sent.append((slot % len(Q), t0 + due[i]))
                    w.late.append(now - (t0 + due[i]))
                    rid = rt.submit(Q[slot % len(Q)], now=now)
                    if rt.result(rid) is None:  # else refused: missing
                        inflight[rid] = slot
                    i += 1
                    now = time.perf_counter()
        with _annotate("poll"):
            done, _ = rt.poll(now=time.perf_counter())
        t_hold = time.perf_counter()
        if done:
            with _annotate("result"):
                for rid in done:
                    slot = inflight.pop(rid)
                    res = rt.result(rid)
                    if res is not None and res.answered:
                        w.answers[slot] = (res.ids, res.scores, t_hold)
            continue
        nxt = t0 + due[i] if i < len(due) else math.inf
        if inflight:
            nxt = min(nxt, t_hold + wait_s)
        pause = nxt - time.perf_counter()
        if pause > 0:
            with _annotate("wait"):
                time.sleep(pause)
    return w, t0


def _hist(rt, name: str):
    m = rt.metrics.get(name)
    return (m.sum(), m.count()) if m is not None else (0.0, 0)


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def run(spec: dict, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        trace_dir: Optional[str] = None) -> dict:
    """One run of a cell; returns the result line as a dict.

    ``t_start`` is the process's start on the ``perf_counter`` clock, so
    that ``setup_s`` covers imports, device start, table, compile and
    warm-up.  Raises `NoDevice` before any work when the chips are
    missing.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    cell, cfg, mix = spec["cell"], spec["config"], spec["mix"]
    devs = devices_for(cell["chips"], require_tpu)
    mesh = sharding = None
    if cell["chips"] > 1:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(cell["chips"])
        sharding = NamedSharding(mesh, P("model", None))

    # ---- set-up: table, queries, runtime, warm-up through the timed path
    table = loadgen.make_table(cfg, seed, sharding)
    if mix["loop"] == "closed":
        due = None
        pool = int(mix["pool"])
        capacity = int(mix["clients"])
    else:
        due = loadgen.open_loop_schedule(mix, seconds)
        pool = len(due)
        capacity = max(1, len(due))
    n_warm = 2 * cfg["lanes"]
    Q, _ = loadgen.make_queries(table, cfg, mix, seed, pool + n_warm)
    Qwarm, Q = Q[pool:], Q[:pool]
    rt = build_runtime(table, cfg, mesh=mesh, seed=seed,
                       capacity=max(capacity, n_warm))
    rt.warmup()
    for q in Qwarm:              # every program of the timed path, once
        rt.submit(q, now=time.perf_counter())
    rt.drain(now=time.perf_counter())
    for rid in range(rt.n_requests):
        rt.result(rid)
    tracer = None
    if trace:                    # spans of the window's requests only
        from repro.obs.trace import SpanTracer
        tracer = rt.tracer = SpanTracer(max_requests=10 ** 6,
                                        max_global_events=10 ** 6)

    # ---- measured window
    disp0, hd0, ho0 = rt.n_dispatches, _hist(rt, "cascade_dispatch_ms"), \
        _hist(rt, "serve_batch_occupancy")
    log_dir = None
    if trace:
        log_dir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    with _annotate("window"):
        if due is None:
            w, t0 = closed_loop(rt, Q, int(mix["clients"]), seconds)
        else:
            w, t0 = open_loop(rt, Q, due, rt.batch_wait_s)
    t_end = max([a[2] for a in w.answers.values()], default=t0)
    if trace:
        jax.profiler.stop_trace()
    window = {"dispatches": rt.n_dispatches - disp0,
              "dispatch_ms": tuple(a - b for a, b in zip(
                  _hist(rt, "cascade_dispatch_ms"), hd0)),
              "occupancy": tuple(a - b for a, b in zip(
                  _hist(rt, "serve_batch_occupancy"), ho0))}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    plan = rt.executor.plan
    final_coverage = cfg["precision"] == "fp32" and not cfg.get("adaptive")
    queued_ms = []
    if tracer is not None:
        queued_ms = [ev["dur"] * 1e-3 for ev in tracer.export()["traceEvents"]
                     if ev.get("name") == "queued"]
    del rt
    gc.collect()

    # ---- end-to-end numbers over every request of the window
    lat = [w.answers[s][2] - w.sent[s][1] for s in w.answers]
    missing = len(w.sent) - len(w.answers)
    p50, p95 = loadgen.percentiles(lat, missing)
    elapsed = max(t_end - t0, 1e-9)
    # an open loop's latencies run from due times under a load that does
    # not wait for answers: they are other metrics than a closed loop's
    lat_name = "latency" if due is None else "open_latency"
    e2e = {"qps": len(w.answers) / elapsed,
           f"{lat_name}_p50_ms": p50 if math.isfinite(p50)
           else elapsed * 1e3,
           f"{lat_name}_p95_ms": p95 if math.isfinite(p95)
           else elapsed * 1e3,
           "setup_s": setup_s}

    # ---- per-layer numbers from the trace and the runtime's counters
    summary = None
    if trace:
        path = xplane.find_trace(log_dir)
        summary = xplane.reduce_trace(xplane.load(path)) if path else None
        if trace_dir is None:
            shutil.rmtree(log_dir, ignore_errors=True)
    ctx = {"trace": summary, "window": window, "queued_ms": queued_ms,
           "plan": plan, "final_coverage": final_coverage,
           "table_itemsize": table.dtype.itemsize,
           "device_kind": devs[0].device_kind}

    # ---- the check: every answer of the window against the reference
    del table
    slots = sorted(w.answers)
    ids = (np.stack([np.asarray(w.answers[s][0]) for s in slots])
           .astype(np.int32) if slots else np.zeros((0, cfg["K"]), np.int32))
    scores = (np.stack([np.asarray(w.answers[s][1]) for s in slots])
              .astype(np.float32) if slots
              else np.zeros((0, cfg["K"]), np.float32))
    Qs = Q[[w.sent[s][0] for s in slots]] if slots else Q[:0]
    ref_table = loadgen.make_table(cfg, seed, sharding)
    top_ids, top_sc, got = reference.exact_answers(
        ref_table, cfg["n_valid"], Qs, ids, cfg["K"])
    numbers = checks.compare(ids, scores, top_ids, top_sc, got,
                             n_valid=cfg["n_valid"], eps=cfg["eps"],
                             value_range=loadgen.value_range(cfg),
                             unanswered=missing)
    limits = cfg["limits"]
    correct = checks.judge(numbers, limits)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            v = _reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if summary is not None:
            device.update(busy_s=summary["busy_s"],
                          window_s=summary["window_s"])
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    out = {"correct": bool(correct), "attempted": len(w.sent),
           "failed": missing, "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    if w.late:
        out["generator_late_ms_max"] = max(w.late) * 1e3
    out["checks"] = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
                     for k, v in checks.report(numbers, limits).items()}
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    """The command line: ``--workload --seed --seconds --trace``."""
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profile here instead of deleting it")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    try:
        import repro.launch.engine  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is missing next to bench/ ({e})",
              file=sys.stderr)
        return 2
    import jax

    cache = ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    spec = load_spec(args.workload)
    try:
        out = run(spec, args.seed, args.seconds, bool(args.trace),
                  t_start=t_start, trace_dir=args.trace_dir)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0

