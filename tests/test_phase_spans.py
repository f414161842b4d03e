"""Phase spans on the profiler's clock and the executor's compile counter.

A tiny serving run (1,024 x 1,536, the size of the benchmark's CPU runs)
is traced with ``jax.profiler.trace`` in a subprocess, on one device, on
a 4-virtual-device mesh, and through the multi-tenant runtime; the
``.xplane.pb`` is read back with ``ProfileData``.  Every span of the
serving path must be there, carry its ``didx`` / ``rid`` argument, and
nest inside the scheduler pass that ran it.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.engine import CascadeExecutor, ServeRuntime
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import counting_compiles, phase

ROOT = Path(__file__).resolve().parent.parent

ROWS, DIM, LANES = 1024, 1536, 8

#: the spans of one dispatch, in the order they run
DISPATCH_SPANS = ("repro.serve.assemble", "repro.executor.h2d",
                  "repro.executor.launch", "repro.executor.wait",
                  "repro.executor.d2h", "repro.serve.finish")

_TRACED = """
import glob, json, os, sys
import numpy as np
import jax
from jax.profiler import ProfileData

kind, devices, log_dir = sys.argv[1], int(sys.argv[2]), sys.argv[3]
rng = np.random.default_rng(0)
table = (0.02 * rng.normal(size=({rows}, {dim}))).astype(np.float32)
Q = rng.uniform(-1, 1, size=({lanes} + 3, {dim})).astype(np.float32)
if kind == "tenancy":
    from repro.launch.tenancy import (MultiTenantRuntime, TableRegistry,
                                      TenantConfig)
    reg = TableRegistry(lanes={lanes})
    reg.register("t", table, TenantConfig(K=1, eps=0.1, delta=0.1,
                                           deadline_ms=0.0,
                                           cache_entries=0))
    rt = MultiTenantRuntime(reg, batch_wait_ms=1e-3)
    submit = lambda q: rt.submit(q, tenant="t")
else:
    from repro.launch.engine import ServeRuntime
    mesh = None
    if devices > 1:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(devices)
    rt = ServeRuntime(table, K=1, eps=0.1, delta=0.1, lanes={lanes},
                      batch_wait_ms=1e-3, cache_entries=0,
                      recall_sample_rate=0.0, mesh=mesh, seed=0)
    submit = rt.submit
rt.warmup()
with jax.profiler.trace(log_dir):
    for occupancy in (1, 3, {lanes}):
        for q in Q[:occupancy]:
            submit(q)
        rt.poll()
    for q in Q[:2]:
        submit(q)
    rt.drain()
path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                 recursive=True)[0]
spans = []
for plane in ProfileData.from_file(path).planes:
    if plane.name.startswith("/host:"):
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.append({{"name": ev.name,
                                   "t0": ev.start_ns,
                                   "t1": ev.start_ns + ev.duration_ns,
                                   "args": {{k: v if isinstance(v, str)
                                             else int(v)
                                             for k, v in ev.stats}}}})
print(json.dumps({{"devices": len(jax.devices()), "spans": spans}}))
"""


def _traced_spans(kind: str, devices: int, log_dir) -> dict:
    flags = f"--xla_force_host_platform_device_count={devices}"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"), XLA_FLAGS=flags)
    code = _TRACED.format(rows=ROWS, dim=DIM, lanes=LANES)
    proc = subprocess.run([sys.executable, "-c", code, kind, str(devices),
                           str(log_dir)], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _inside(inner: dict, outer: dict) -> bool:
    return outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]


@pytest.mark.parametrize("kind,devices", [("runtime", 1), ("runtime", 4),
                                          ("tenancy", 1)])
def test_every_phase_span_is_recorded_nested_with_its_args(
        tmp_path, kind, devices):
    out = _traced_spans(kind, devices, tmp_path)
    assert out["devices"] == devices
    spans = out["spans"]
    polls = [s for s in spans if s["name"] == "repro.serve.poll"]
    submits = [s for s in spans if s["name"] == "repro.serve.submit"]
    assert len(polls) == 4                  # three polls and one drain
    # one submit span per request, each with its own rid, none in a poll
    assert sorted(s["args"]["rid"] for s in submits) == \
        list(range(len(submits)))
    assert len(submits) == 1 + 3 + LANES + 2
    assert not any(_inside(s, p) for s in submits for p in polls)
    by_didx = {}
    for s in spans:
        if s["name"] in DISPATCH_SPANS:
            by_didx.setdefault(s["args"]["didx"], []).append(s)
    assert sorted(by_didx) == [0, 1, 2, 3]  # one per poll, drain included
    # the launch span names the lanes filled and how the table is stored
    filled = {}
    for s in spans:
        if s["name"] == "repro.executor.launch":
            assert s["args"]["table_dtype"] == "float32"
            filled[s["args"]["didx"]] = s["args"]["lanes_filled"]
    assert filled == {0: 1, 1: 3, 2: LANES, 3: 2}
    for didx, group in by_didx.items():
        group.sort(key=lambda s: s["t0"])
        assert tuple(s["name"] for s in group) == DISPATCH_SPANS, didx
        for a, b in zip(group, group[1:]):
            assert a["t1"] <= b["t0"]       # siblings, in order
        assert any(all(_inside(s, p) for s in group) for p in polls), didx


def test_phase_is_inert_without_a_profiler():
    with phase("serve.poll"), phase("executor.h2d", didx=3):
        pass


def test_counting_compiles_counts_only_inside_the_block():
    reg = MetricsRegistry()
    c = reg.counter("c_total", labels=("k",))
    f = jax.jit(lambda x: x * 3 + 1)
    x = np.arange(5, dtype=np.float32)
    f(x)                                    # compiled outside: not counted
    assert c.get(k="a") == 0
    g = jax.jit(lambda x: x * 5 - 2)
    with counting_compiles(c, k="a"):
        g(x)
        g(x)                                # cached: no second compile
    assert c.get(k="a") == 1
    h = jax.jit(lambda x: x - 7)
    with counting_compiles(c, k="a"):
        with counting_compiles(c, k="b"):
            h(x)                            # the inner block takes it
    assert (c.get(k="a"), c.get(k="b")) == (1, 1)


def _table(seed=0):
    rng = np.random.default_rng(seed)
    return (0.02 * rng.normal(size=(ROWS, DIM))).astype(np.float32)


def test_a_fresh_executor_counts_its_first_compile():
    ex = CascadeExecutor(_table(), K=1, eps=0.1, delta=0.1, lanes=LANES)
    c = ex.metrics.get("cascade_compiles_total")
    assert c.total() == 0
    Q = np.zeros((LANES, DIM), np.float32)
    ex.dispatch(Q, jax.random.PRNGKey(0))
    assert c.total() >= 1
    n = c.total()
    ex.dispatch(Q, jax.random.PRNGKey(1))
    assert c.total() == n
    # the same labels as the dispatch counter
    dispatches = ex.metrics.get("cascade_dispatches_total")
    assert [lbl for lbl, _ in c.rows()] == \
        [lbl for lbl, _ in dispatches.rows()]


def test_the_executor_counts_the_plans_pulls_and_the_dense_share():
    """Each dispatch adds lanes x the flat schedule's pulls, and lanes x
    round 1's dense pulls, with the dispatch counter's labels."""
    from repro.core.boundedme_jax import fused_flat_schedule

    ex = CascadeExecutor(_table(), K=1, eps=0.1, delta=0.1, lanes=LANES)
    flat = fused_flat_schedule(ex.plan, final_exact=True)
    assert 0 < flat.dense_pulls < flat.pull_steps     # later rounds pull
    pulls = ex.metrics.get("cascade_pulls_total")
    dense = ex.metrics.get("cascade_dense_pulls_total")
    assert (pulls.total(), dense.total()) == (0, 0)
    Q = np.zeros((LANES, DIM), np.float32)
    for k in range(2):
        ex.dispatch(Q, jax.random.PRNGKey(k))
    assert pulls.total() == 2 * LANES * flat.pull_steps
    assert dense.total() == 2 * LANES * flat.dense_pulls
    dispatches = ex.metrics.get("cascade_dispatches_total")
    for c in (pulls, dense):
        assert [lbl for lbl, _ in c.rows()] == \
            [lbl for lbl, _ in dispatches.rows()]


@pytest.mark.parametrize("dim,engaged", [(DIM, True), (512, False)])
def test_the_executor_counts_the_kernels_pull_groups(dim, engaged):
    """Each dispatch adds lanes x the grid steps that pull a group of up
    to 16 same-column survivor tiles after round 1, with the dispatch
    counter's labels; a table one block wide pulls nothing after round
    1, and counts none."""
    from repro.core.boundedme_jax import fused_flat_schedule

    ex = CascadeExecutor(_table()[:, :dim], K=1, eps=0.1, delta=0.1,
                         lanes=LANES)
    flat = fused_flat_schedule(ex.plan, final_exact=True)
    size = flat.groups(16)[1]
    groups = ex.metrics.get("cascade_pull_groups_total")
    assert groups.total() == 0
    Q = np.zeros((LANES, dim), np.float32)
    for k in range(2):
        ex.dispatch(Q, jax.random.PRNGKey(k))
    per_lane = int((size > 0).sum())
    assert groups.total() == 2 * LANES * per_lane
    assert (per_lane > 0) == engaged
    if engaged:   # more than one tile a group on average
        later = (ex.metrics.get("cascade_pulls_total").total()
                 - ex.metrics.get("cascade_dense_pulls_total").total())
        assert later == 2 * LANES * int(size.sum()) > groups.total()
    dispatches = ex.metrics.get("cascade_dispatches_total")
    assert [lbl for lbl, _ in groups.rows()] == \
        [lbl for lbl, _ in dispatches.rows()]


def _read_bytes_per_dispatch(table, filled=None, **kw):
    """(bytes one dispatch adds to ``cascade_table_read_bytes_total``,
    the executor): a direct dispatch, or one of ``filled`` lanes."""
    from repro.launch.engine import dispatch_with_retries

    ex = CascadeExecutor(table, K=1, eps=0.1, delta=0.1, lanes=LANES,
                         value_range=0.24, **kw)
    c = ex.metrics.get("cascade_table_read_bytes_total")
    assert c.total() == 0
    Q = np.zeros((LANES, DIM), np.float32)
    if filled is None:
        ex.dispatch(Q, jax.random.PRNGKey(0))
    else:
        dispatch_with_retries(ex, Q, jax.random.PRNGKey(0), didx=0,
                              filled=filled)
    dispatches = ex.metrics.get("cascade_dispatches_total")
    assert [lbl for lbl, _ in c.rows()] == \
        [lbl for lbl, _ in dispatches.rows()]
    return c.total(), ex


@pytest.mark.parametrize("dtype,precision,itemsize", [
    ("float32", "fp32", 4), ("bfloat16", "fp32", 2), ("float32", "int8", 1)])
def test_the_executor_counts_the_table_bytes_its_plan_reads(
        dtype, precision, itemsize):
    """Round 1's dense slabs once for the batch, then every lane's own
    pulls, each slab at the width the table (or its codes) is stored."""
    from repro.core.boundedme_jax import fused_flat_schedule

    got, ex = _read_bytes_per_dispatch(
        _table().astype(jnp.dtype(dtype)), precision=precision)
    flat = fused_flat_schedule(ex.plan, final_exact=True)
    slab = ex.plan.tile * ex.plan.block * itemsize
    per_query = flat.pull_steps - flat.dense_pulls
    assert 0 < flat.dense_pulls and 0 < per_query
    assert got == flat.t_dense * flat.n_dense * slab + LANES * per_query * slab


def test_a_bf16_table_reads_half_the_bytes_of_the_same_plan_in_fp32():
    fp32, ex32 = _read_bytes_per_dispatch(_table())
    bf16, ex16 = _read_bytes_per_dispatch(_table().astype(jnp.bfloat16))
    assert ex16.plan == ex32.plan
    assert bf16 * 2 == fp32 > 0


@pytest.mark.parametrize("filled", [1, 3, LANES])
def test_a_partial_batch_counts_only_its_filled_lanes(filled):
    from repro.core.boundedme_jax import fused_flat_schedule

    got, ex = _read_bytes_per_dispatch(_table(), filled=filled)
    flat = fused_flat_schedule(ex.plan, final_exact=True)
    slab = ex.plan.tile * ex.plan.block * 4
    assert got == (flat.dense_pulls
                   + filled * (flat.pull_steps - flat.dense_pulls)) * slab


def test_a_warmed_runtime_compiles_nothing_at_any_occupancy():
    rt = ServeRuntime(_table(1), K=1, eps=0.1, delta=0.1, lanes=LANES,
                      batch_wait_ms=1e-3, cache_entries=0,
                      recall_sample_rate=0.0, seed=0)
    rt.warmup()
    c = rt.metrics.get("cascade_compiles_total")
    warm = c.total()
    assert warm >= 1
    rng = np.random.default_rng(2)
    for occupancy in range(1, LANES + 1):
        Q = rng.uniform(-1, 1, size=(occupancy, DIM)).astype(np.float32)
        rids = [rt.submit(q, now=0.0) for q in Q]
        done, _ = rt.drain(now=1.0)
        assert sorted(done) == rids
        assert c.total() == warm, occupancy
    assert rt.stats()["lanes"]["mean_occupancy"] == (LANES + 1) / 2
