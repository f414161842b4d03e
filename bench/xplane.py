"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

`load` reads the trace with ``jax.profiler.ProfileData`` into plain
records; everything after that is arithmetic on lists, so the reduction
is tested on a recorded trace and on hand-made records alike.

* Device ops are the events of each TPU plane's "XLA Ops" line, named by
  their HLO text.  An op is the fused cascade kernel when it is the
  Pallas custom call (`is_kernel`), a collective when its opcode is one
  (`is_collective`), and the driver's otherwise when it runs inside the
  executor's flush program, an event of the "XLA Modules" line
  (`is_flush`).
* Host phases are the benchmark's own ``jax.profiler.TraceAnnotation``
  spans, named ``bench.<phase>``; ``bench.window`` brackets the measured
  window, and the reduction counts only what lies inside it.
* Busy time is the union of a device's op intervals; idle gaps are the
  holes in that union, each named by the host phase that overlaps it
  most.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
_COLLECTIVE = re.compile(r"\b(all-gather|all-reduce|reduce-scatter|"
                         r"all-to-all|collective-permute|collective-broadcast)"
                         r"(-start|-done)?\(")
_HLO_NAME = re.compile(r"%?([\w.\-]+)\s*=")


def find_trace(log_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def short_name(text: str) -> str:
    """An op's HLO name from the trace's ``%name = shape opcode(...)``."""
    m = _HLO_NAME.match(text)
    return m.group(1) if m else text


def load(path: str) -> dict:
    """Plain records of a trace: device ops per device and host spans.

    Returns ``{"devices": {plane_name: [op, ...]}, "host": [span, ...]}``
    with ``op = {"name", "text", "t0", "t1", "module"}`` and
    ``span = {"name", "t0", "t1"}``, times in ns.  An op's ``module`` is
    the XLA module (``jit_<function>``) running on its device when it
    started.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[dict]] = {}
    host: List[dict] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                           e.name.split("(")[0])
                          for e in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            ops = devices.setdefault(plane.name, [])
            for ev in lines.get("XLA Ops", []):
                t0 = float(ev.start_ns)
                i = bisect.bisect_right(starts, t0) - 1
                module = mods[i][2] if i >= 0 and t0 <= mods[i][1] else ""
                ops.append({"name": short_name(ev.name), "text": ev.name,
                            "t0": t0, "t1": t0 + float(ev.duration_ns),
                            "module": module})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append({"name": ev.name,
                                     "t0": float(ev.start_ns),
                                     "t1": float(ev.start_ns
                                                 + ev.duration_ns)})
    return {"devices": devices, "host": host}


def is_kernel(op: dict) -> bool:
    """The Pallas kernel: the flush's TPU custom call."""
    return "custom-call(" in op["text"] or "tpu_custom_call" in op["text"]


def is_collective(op: dict) -> bool:
    """A collective between chips, by its HLO opcode."""
    return _COLLECTIVE.search(op["text"]) is not None


def is_flush(op: dict) -> bool:
    """An op of the executor's flush program (its jit is ``_flush_fn``)."""
    return op["module"] == "jit__flush_fn"


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(op: dict, w0: float, w1: float) -> float:
    return max(0.0, min(op["t1"], w1) - max(op["t0"], w0))


def window_of(host: List[dict]) -> Optional[Tuple[float, float]]:
    """The measured window's (t0, t1), or None if it was not traced."""
    spans = [s for s in host if s["name"] == WINDOW]
    if not spans:
        return None
    return min(s["t0"] for s in spans), max(s["t1"] for s in spans)


def phase_of(gap: Tuple[float, float], host: List[dict]) -> str:
    """The host phase overlapping most of ``gap`` ("none" if no phase)."""
    best, name = 0.0, "none"
    for s in host:
        if s["name"] == WINDOW:
            continue
        ov = min(gap[1], s["t1"]) - max(gap[0], s["t0"])
        if ov > best:
            best, name = ov, s["name"][len("bench."):]
    return name


def reduce_trace(rec: dict, top: int = 10) -> Optional[dict]:
    """Window totals of a trace's records, averaged over the devices.

    Returns None when the trace holds no window or no device op in it.
    Seconds: ``window_s``, ``busy_s`` (union of op intervals),
    ``kernel_s``, ``collective_s``, ``driver_s`` (flush ops outside the
    two), per device on average; counts: ``kernel_ops``, ``devices``;
    ``device_ops`` (top ops by time, per device on average) and
    ``idle_gaps`` (longest holes on the first device, by host phase).
    """
    win = window_of(rec["host"])
    devices = sorted(rec["devices"])
    if win is None or not devices:
        return None
    w0, w1 = win
    busy = kern = coll = drv = 0.0
    n_kernel = 0
    by_name: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for i, dev in enumerate(devices):
        ops = [op for op in rec["devices"][dev] if _clip(op, w0, w1) > 0]
        cover = union([(max(op["t0"], w0), min(op["t1"], w1)) for op in ops])
        busy += sum(b - a for a, b in cover)
        for op in ops:
            dt = _clip(op, w0, w1)
            by_name[op["name"]] = by_name.get(op["name"], 0.0) + dt
            if is_kernel(op):
                kern += dt
                n_kernel += 1
            elif is_collective(op):
                coll += dt
            elif is_flush(op):
                drv += dt
        if i == 0:
            edges = [w0] + [x for ab in cover for x in ab] + [w1]
            holes = [(edges[j], edges[j + 1])
                     for j in range(0, len(edges), 2)
                     if edges[j + 1] > edges[j]]
            gaps = sorted(((phase_of(h, rec["host"]), (h[1] - h[0]) * 1e-9)
                           for h in holes), key=lambda g: -g[1])[:top]
    n = len(devices)
    if busy <= 0.0:
        return None
    ops_top = sorted(((k, v * 1e-9 / n) for k, v in by_name.items()),
                     key=lambda kv: -kv[1])[:top]
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy * 1e-9 / n,
            "kernel_s": kern * 1e-9 / n, "collective_s": coll * 1e-9 / n,
            "driver_s": drv * 1e-9 / n, "kernel_ops": n_kernel,
            "devices": n, "device_ops": [list(x) for x in ops_top],
            "idle_gaps": [list(g) for g in gaps]}
