"""Traffic of the benchmark: item tables, queries and arrival times, from a seed.

Everything a run sends is a pure function of ``--seed`` and the data files
(`bench/configs/<config>.json`, `bench/traffic/<traffic>.json`):

* the item table: ``rows x d`` drawn as the repo's ``init_params``
  draws an embedding (fp32 standard normal times ``init_std``), truncated
  at ``trunc_sigmas`` so that the configuration's stated value range
  ``2 * qmax_hint * trunc_sigmas * init_std`` bounds every product a
  query can make with it.  It is stored as the configuration's
  ``table_dtype`` says (`table_dtype`): fp32 as drawn, or the draw
  rounded to bf16 as a checkpoint's cast rounds it;
* the queries: coordinates uniform in ``[-qmax_hint, qmax_hint]`` that lean
  towards one target row each, so that the target's exact mean product
  stands ``z`` standard deviations above the other rows' (``z`` drawn per
  query from the mix's ``target_z`` range).  A decoder's hidden state
  leans so towards its next token; the lean is what lets a check tell a
  served top-K from any K rows;
* open-loop arrival times (`arrival_trace`, copied from
  ``repro.launch.serve`` so that later program changes cannot move it),
  drawn from the mix's own ``arrival_seed`` and not from ``--seed``: the
  tail of a queue near capacity turns on where the few dense clusters of
  arrivals fall, so arrivals drawn anew for each seed would move the
  tails by more than any change worth seeing.  Every run of a mix offers
  the same arrivals; the seed sets the queries and the table.

Nothing here imports the program.  The device work (table, query pool) is
one jitted call each, made at set-up.
"""

from __future__ import annotations

import math
from typing import Tuple

import numpy as np

_TRACE_ROOT = 0x7AC3     # the seed root `repro.launch.serve` uses


def seed_words(seed: int, purpose: int) -> int:
    """A 32-bit key word for one use of ``seed``.

    ``seed`` may be any non-negative integer, wider than 32 bits too.
    """
    return int(np.random.SeedSequence([int(seed), int(purpose)])
               .generate_state(1)[0])


TABLE, QUERIES, RUNTIME = 1, 2, 3


def value_range(cfg: dict) -> float:
    """The product range the configuration states and the table obeys."""
    return 2.0 * cfg["qmax_hint"] * cfg["trunc_sigmas"] * cfg["init_std"]


#: how a configuration's ``table_dtype`` may store the table
TABLE_DTYPES = ("float32", "bfloat16")


def table_dtype(cfg: dict) -> str:
    """The configuration's ``table_dtype``: how the table is stored.

    It states storage only (``precision`` states the pulls' arithmetic);
    a file without the key stores fp32.
    """
    name = cfg.get("table_dtype", "float32")
    if name not in TABLE_DTYPES:
        raise ValueError(f"table_dtype {name!r} in configuration "
                         f"{cfg.get('name')!r}: use one of {TABLE_DTYPES}")
    return name


def bf16_cap(x: float) -> float:
    """The largest bf16 value not above ``x`` (``x`` > 0)."""
    import ml_dtypes

    b = np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)
    if float(b) > x:
        b = np.nextafter(b, np.zeros_like(b))
    return float(b)


def make_table(cfg: dict, seed: int, sharding=None):
    """The (rows, d) item table on the device, in one jitted call.

    A bf16 table is the fp32 draw rounded to nearest-even, then clipped
    to the largest bf16 value not above ``trunc_sigmas * init_std``, so
    that `value_range` still bounds every product.  With ``sharding`` the
    table is created already laid out by it.
    """
    import jax
    import jax.numpy as jnp

    rows, d = cfg["rows"], cfg["d"]
    lim = float(cfg["trunc_sigmas"])
    std = float(cfg["init_std"])
    bf16 = table_dtype(cfg) == "bfloat16"
    cap = bf16_cap(lim * std) if bf16 else None

    def draw(key):
        z = jax.random.normal(key, (rows, d), jnp.float32)
        v = jnp.clip(z, -lim, lim) * jnp.float32(std)
        if bf16:
            c = jnp.bfloat16(cap)
            v = jnp.clip(v.astype(jnp.bfloat16), -c, c)
        return v

    fn = jax.jit(draw, out_shardings=sharding)
    return fn(jax.random.PRNGKey(seed_words(seed, TABLE)))


def target_beta(z, d: int, qmax: float):
    """Lean that puts the target ``z`` std above the others (no clipping)."""
    z = np.asarray(z, np.float64)
    return z * qmax / np.sqrt(3.0 * (d - z * z))


def make_queries(table, cfg: dict, mix: dict, seed: int, count: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """``count`` queries (host fp32) and their target rows, in one call.

    Query i is ``clip(u_i + beta_i * v_t / init_std, +-qmax)`` with
    ``u_i`` uniform in the box, ``v_t`` the target row as stored, widened
    to fp32, and ``beta_i`` from `target_beta` at ``z_i ~ U(target_z)``.
    """
    import jax
    import jax.numpy as jnp

    d, qmax = cfg["d"], float(cfg["qmax_hint"])
    rng = np.random.default_rng(seed_words(seed, QUERIES))
    targets = rng.integers(0, cfg["n_valid"], size=count).astype(np.int32)
    lo, hi = mix["target_z"]
    z = rng.uniform(lo, hi, size=count)
    beta = target_beta(z, d, qmax).astype(np.float32)

    @jax.jit
    def build(V, key, tgt, beta):
        u = jax.random.uniform(key, (tgt.shape[0], d), jnp.float32,
                               -qmax, qmax)
        lean = V[tgt].astype(jnp.float32) * (beta[:, None]
                                             / jnp.float32(cfg["init_std"]))
        return jnp.clip(u + lean, -qmax, qmax)

    key = jax.random.PRNGKey(seed_words(seed, QUERIES))
    Q = build(table, key, jnp.asarray(targets), jnp.asarray(beta))
    return np.asarray(jax.device_get(Q)), targets


def arrival_trace(n: int, *, interarrival_ms: float = 0.1,
                  pattern: str = "uniform", seed: int = 0,
                  burst_factor: float = 8.0, burst_len: int = 16,
                  tail: float = 1.5) -> np.ndarray:
    """Reproducible (n,) arrival times in seconds for a query stream.

    A copy of ``repro.launch.serve.arrival_trace``: ``uniform`` spaces
    arrivals ``interarrival_ms`` apart, ``poisson`` draws i.i.d.
    exponential gaps, ``bursty`` alternates geometric-length bursts
    (``interarrival_ms / burst_factor`` apart) with Pareto(``tail``)
    quiet gaps.  A pure function of its arguments.
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
            gaps[i] = (0.0 if i == 0
                       else d * burst_len * (0.5 + rng.pareto(tail)))
            gaps[i + 1:i + blen] = d / burst_factor
            i += blen
        return np.cumsum(gaps)
    raise ValueError(f"unknown arrival pattern {pattern!r}; "
                     f"use uniform | poisson | bursty")


def open_loop_schedule(mix: dict, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open-loop mix.

    The arrivals of ``arrival_trace`` at the mix's rate, pattern and
    ``arrival_seed`` that fall inside ``seconds``.
    """
    rate = float(mix["rate_qps"])
    n = int(math.ceil(rate * seconds * 1.5)) + 64
    t = arrival_trace(n, interarrival_ms=1e3 / rate,
                      pattern=mix["arrivals"],
                      seed=int(mix["arrival_seed"]))
    return t[t < seconds]


def percentiles(latencies_s, missing: int, qs=(50.0, 95.0)):
    """Latency percentiles (ms) over every request of a window.

    ``missing`` requests (refused, failed or never answered) count as
    slower than any answered one: a percentile that lands on one is
    ``inf``.
    """
    lat = np.sort(np.asarray(latencies_s, np.float64) * 1e3)
    total = lat.size + int(missing)
    if total == 0:
        return [float("nan")] * len(qs)
    full = np.concatenate([lat, np.full(int(missing), np.inf)])
    out = []
    for q in qs:
        # nearest rank: the smallest value with at least q% at or below it
        rank = max(1, int(math.ceil(q / 100.0 * total)))
        out.append(float(full[rank - 1]))
    return out
