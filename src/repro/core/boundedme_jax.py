"""BoundedME, TPU-native: blocked pulls, tile elimination, static schedule.

This is the optimized JAX/Pallas path (DESIGN.md §3).  The elimination
schedule is computed at *trace time* (it is data-independent), so the whole
bandit compiles to a fixed cascade of gather + tile-matmul + top_k ops with
static shapes — jit/pjit/vmap-able and shardable.

Two execution strategies share the same static plan:

  * ``use_pallas=True`` — the whole cascade (every pull round, every tile
    elimination, the final top-K) runs as ONE fused Pallas kernel
    (`repro.kernels.fused_cascade`): dispatch count per query is 1
    regardless of round count, and the accumulator/survivor state stays
    on-chip across rounds;
  * ``use_pallas=False`` — a pure-jnp fallback that walks the same
    flattened schedule with a `lax.scan` over each round's coordinate
    blocks.  It gathers one (T, R, C) slab per block and never materializes
    the old (T, dt, R, C) per-round gather.

Adaptations versus the reference (`repro.core.boundedme`):
  * a pull = one coordinate *block* of ``block`` (default 512) entries,
    computed as an MXU tile-dot; the without-replacement bound applies with
    N -> N//block and block-mean rewards;
  * arms are eliminated in *tiles* of ``tile`` (default 8) rows ranked by
    the tile-max empirical mean (the running empirical argmax always
    survives); the reference path keeps exact per-arm semantics;
  * one shared random block permutation per query (uniform without
    replacement marginally per arm; contiguity for HBM).

``precision='int8'`` (DESIGN.md §10) runs every sampling round in int8:
the table is quantized per (tile, block) cell (`repro.core.quantize`),
pulls run int8 x int8 -> int32 and dequantize into the f32 accumulator,
and the schedule's confidence radii are widened by the worst-case
quantization bias (`make_schedule(quant_err=...)`) so the (eps, delta)
calibration survives.  ``precision='int4'`` halves the pulled bytes again
(nibble-packed tiles, W4A8 dots under the 15-level worst-case bias), and
``precision='pq'`` replaces scalar codes with per-subspace product
quantization (uint8 codes + LUT tile-dots, `pq_subdims` bytes -> 1): pq
has no closed-form bias bound, so its plans carry the **measured**
per-pull error (`measured_plan_quant_err` / `make_measured_plan`),
inflated by a safety factor, into ``make_schedule(quant_err=...)`` —
``Schedule.eps_effective`` stays honest either way.  The final top-K
candidates are always rescored in fp32 against the unquantized table when
``final_exact=True``, so returned scores carry no quantization error at
all, on every tier.
"""

from __future__ import annotations

import dataclasses
import math
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bounds
from repro.core.quantize import (f32_tile_dot, measured_quant_err,
                                 pq_encode, pq_tile_dot, pq_train,
                                 quantize_blocks, quantize_tiles,
                                 quantize_tiles_int4, unpack_int4)
from repro.core.schedule import (FlatSchedule, Schedule, cert_coeffs,
                                 flatten_schedule, make_schedule)

__all__ = ["BlockedPlan", "make_plan", "choose_pull_mode",
           "measured_plan_quant_err", "make_measured_plan",
           "fused_flat_schedule", "fused_pull_group", "bounded_me_blocked",
           "bounded_me_batched", "bounded_me_decode"]


@dataclasses.dataclass(frozen=True)
class BlockedPlan:
    """Static geometry + schedule for the blocked TPU path."""

    n: int              # true number of arms
    N: int              # true vector dimension
    K: int
    tile: int           # arm-tile rows (elimination granularity)
    block: int          # coordinate-block width (pull granularity)
    n_tiles: int        # padded arm tiles
    n_blocks: int       # padded coordinate blocks
    schedule: Schedule  # over (n_tiles "arms", n_blocks "rewards", K_tiles)
    precision: str = "fp32"   # sampling arithmetic:
    #                           'fp32' | 'int8' | 'int4' | 'pq' (§10)
    pull_mode: str = "row"    # resolved reward stream: 'row' | 'coord' (§14)
    pq_subdims: int = 8       # pq subspace width w (codes per row = block/w)
    pq_codes: int = 16        # pq codebook size (uint8 codes, <= 256)

    @property
    def k_tiles(self) -> int:
        """Arm tiles that must survive to the final round: min(n_tiles, K).

        In the worst case each of the top-K arms sits in its own tile, so
        min(n_tiles, K) tiles must survive to the end (ceil(K/tile) would
        lose winners under adversarial placement).
        """
        return min(self.n_tiles, self.K)

    @property
    def k_out_cap(self) -> int:
        """Widest final extraction the cascade supports (`k_out` upper bound).

        The final top-K scans the ``n_final`` surviving tiles, i.e.
        ``n_final * tile`` candidate rows; no more than that many candidates
        exist to extract (padding rows included — callers mask those).
        """
        n_final = (self.schedule.rounds[-1].n_keep if self.schedule.rounds
                   else self.n_tiles)
        return n_final * self.tile

    @property
    def quant_err(self) -> float:
        """Per-block-mean quantization bias the schedule absorbs (0 = fp32)."""
        return self.schedule.quant_err

    @property
    def eps_effective(self) -> float:
        """Honest end-to-end eps bound incl. quantization (== eps at fp32).

        See `Schedule.eps_effective` and DESIGN.md §10: rounds whose
        budget absorbs the int8 bias stay eps_l-correct; saturated rounds
        contribute at most ``2 * quant_err`` each.
        """
        return self.schedule.eps_effective

    @property
    def total_multiplies(self) -> int:
        """FLOP-level sample complexity of the blocked schedule."""
        per_pull = self.tile * self.block
        return self.schedule.total_pulls * per_pull

    @property
    def naive_multiplies(self) -> int:
        """FLOPs of the exhaustive (n x N) matvec baseline."""
        return self.n * self.N

    @property
    def speedup(self) -> float:
        """FLOP-level speedup of the blocked schedule over exhaustive."""
        return self.naive_multiplies / max(1, self.total_multiplies)


def choose_pull_mode(row_plan: BlockedPlan, coord_plan: BlockedPlan, *,
                     row_margin: float = 0.10) -> str:
    """The hybrid dispatcher's decision rule (DESIGN.md §14, TUNING.md).

    Given the two fully priced candidate plans for the same
    ``(n, d, K, eps, delta)`` query geometry, returns ``'row'`` or
    ``'coord'`` — whichever plan's certified ``total_multiplies`` (the
    width-weighted cost `Schedule.total_coords` times the arm-tile rows)
    is cheaper.  Row pulls are wider MXU tile-dots with better hardware
    utilization per multiply, so row mode is preferred whenever it is
    within ``row_margin`` (default 10%) of the coordinate plan; coord
    mode must beat row by more than the margin to win.  By construction
    the hybrid plan is therefore never more than ``row_margin`` worse
    than the better single mode — in multiplies, before hardware
    effects that favor the row shape further.
    """
    if not 0.0 <= row_margin:
        raise ValueError(f"row_margin must be >= 0, got {row_margin}")
    row_cost = row_plan.total_multiplies
    coord_cost = coord_plan.total_multiplies
    return "row" if row_cost <= coord_cost * (1.0 + row_margin) else "coord"


def make_plan(n: int, N: int, K: int = 1, eps: float = 0.1, delta: float = 0.05,
              value_range: float = 1.0, tile: int = 8, block: int = 512,
              range_mode: str = "clt",
              precision: str = "fp32",
              bound: str = "hoeffding",
              pull_mode: str = "row",
              coord_block: int = 128,
              quant_err: Optional[float] = None,
              pq_subdims: int = 8,
              pq_codes: int = 16) -> BlockedPlan:
    """Build the static plan.

    pull_mode:
      * 'row' (default) — pulls sample whole feature blocks of width
        ``min(block, N)`` per arm tile; per-pull cost grows with d until
        the block cap.
      * 'coord' — the BanditMIPS coordinate estimator (DESIGN.md §14):
        pulls sample *narrow* feature blocks of width ``min(coord_block,
        N)`` without replacement under a shared per-query permutation,
        so the schedule's reward population is ``n_blocks = ceil(N /
        coord_block)`` and the certified pull cost becomes sublinear in
        d.  Same kernel, same bounds — only the block geometry changes.
      * 'hybrid' — prices BOTH candidate plans and returns the cheaper
        by `choose_pull_mode` (row preferred within a 10% multiply
        margin, since row pulls are wider MXU tile-dots); the returned
        plan's ``pull_mode`` is the resolved concrete mode.

    range_mode:
      * 'exact' — block means are bounded by the per-coordinate product range
        (strictly valid, maximally conservative: blocking buys no statistical
        tightening, only MXU efficiency);
      * 'clt' (default) — block means of ``block`` weakly-dependent products
        concentrate ~ range/sqrt(block); the (eps, delta) knob is then
        calibrated on this tighter effective range.  This is a modeling
        assumption (same spirit as the paper's rewards-in-[0,1] assumption)
        and is validated empirically by the fig-1 harness.

    precision:
      * 'fp32' (default) — sampling rounds pull fp32 tiles;
      * 'int8' — sampling rounds pull int8-quantized tiles and the
        schedule's confidence radii are widened by the worst-case
        quantization bias (`bounds.quantization_error`, scaled like the
        value range under ``range_mode``), so the (eps, delta) calibration
        survives quantization (DESIGN.md §10).  Final candidates are
        rescored in fp32 whenever ``final_exact=True``.
      * 'int4' — nibble-packed tiles (half the int8 bytes per pull) under
        the 15-level worst-case bias by default; ``block`` must be even.
      * 'pq' — per-subspace product quantization (``block / pq_subdims``
        bytes per row per pull).  No closed-form bias exists, so a
        **measured** ``quant_err`` is REQUIRED — pass the output of
        `measured_plan_quant_err`, or build the plan with
        `make_measured_plan` which calibrates it for you.

    quant_err:
      Explicit per-pull bias bound on the block-mean scale (what
      `measured_quant_err` returns).  When given it feeds
      ``make_schedule(quant_err=...)`` as-is — NO ``range_mode`` rescale,
      the measurement already lives on the block-mean scale — and
      overrides the tier's worst-case default.  The measured-vs-worst-case
      trade is DESIGN.md §10: measured bounds are far tighter (so rounds
      keep their full deviation budget) but only as representative as the
      calibration queries; the safety factor covers the gap.

    bound:
      * 'hoeffding' (default) — the adaptive path certifies early exit
        with the schedule's own Hoeffding–Serfling radii (zero extra delta
        cost; the round plan is identical to the non-adaptive one);
      * 'bernstein' — certification uses the variance-aware empirical
        Bernstein–Serfling radii with per-tile running mean/M2
        accumulators (`repro.core.schedule.cert_coeffs`, DESIGN.md §12).
    """
    if pull_mode == "hybrid":
        kwargs = dict(K=K, eps=eps, delta=delta, value_range=value_range,
                      tile=tile, range_mode=range_mode, precision=precision,
                      bound=bound, coord_block=coord_block,
                      quant_err=quant_err, pq_subdims=pq_subdims,
                      pq_codes=pq_codes)
        row_plan = make_plan(n, N, block=block, pull_mode="row", **kwargs)
        coord_plan = make_plan(n, N, block=block, pull_mode="coord", **kwargs)
        winner = choose_pull_mode(row_plan, coord_plan)
        return row_plan if winner == "row" else coord_plan
    if pull_mode == "coord":
        if coord_block < 1:
            raise ValueError(f"coord_block must be >= 1, got {coord_block}")
        block = coord_block       # narrow feature tiles: N becomes d_blocks
    elif pull_mode != "row":
        raise ValueError(f"unknown pull_mode {pull_mode!r} "
                         f"(expected 'row', 'coord' or 'hybrid')")
    block = min(block, N)
    tile = min(tile, n)
    n_tiles = -(-n // tile)
    n_blocks = -(-N // block)
    k_tiles = min(n_tiles, K)
    if precision not in ("fp32", "int8", "int4", "pq"):
        raise ValueError(f"unknown precision {precision!r} "
                         f"(expected 'fp32', 'int8', 'int4' or 'pq')")
    if precision == "int4" and block % 2 != 0:
        raise ValueError(f"precision='int4' needs an even pull width to "
                         f"nibble-pack, got block={block}")
    if precision == "pq":
        if not 1 <= pq_subdims or block % pq_subdims != 0:
            raise ValueError(f"precision='pq' needs pull width divisible "
                             f"by pq_subdims, got block={block}, "
                             f"pq_subdims={pq_subdims}")
        if not 1 <= pq_codes <= 256:
            raise ValueError(f"pq_codes must be in [1, 256], got {pq_codes}")
        if quant_err is None:
            raise ValueError(
                "precision='pq' has no closed-form error bound: pass "
                "quant_err=measured_plan_quant_err(V, precision='pq', ...) "
                "or build the plan with make_measured_plan(V, ...)")
    if quant_err is not None:
        if quant_err < 0:
            raise ValueError(f"quant_err must be >= 0, got {quant_err}")
        qerr = float(quant_err)  # measured, already on the block-mean scale
    elif precision in ("int8", "int4"):
        qerr = bounds.quantization_error(value_range,
                                         bits=8 if precision == "int8" else 4)
    else:
        qerr = 0.0
    if range_mode == "clt":
        eff_range = value_range / math.sqrt(block)
        if quant_err is None:
            qerr = qerr / math.sqrt(block)   # the bias concentrates like the
            # products themselves: rounding errors are weakly dependent across
            # the block, so the block-mean bias shrinks ~ 1/sqrt(block) under
            # the same modeling assumption as eff_range.  A measured qerr is
            # NOT rescaled: it is already a block-mean quantity.
    elif range_mode == "exact":
        eff_range = value_range
    else:
        raise ValueError(f"unknown range_mode {range_mode!r}")
    sched = make_schedule(n_tiles, n_blocks, K=k_tiles, eps=eps, delta=delta,
                          value_range=eff_range, quant_err=qerr, bound=bound,
                          pull_mode=pull_mode, pull_width=block)
    return BlockedPlan(n=n, N=N, K=K, tile=tile, block=block, n_tiles=n_tiles,
                       n_blocks=n_blocks, schedule=sched, precision=precision,
                       pull_mode=pull_mode, pq_subdims=pq_subdims,
                       pq_codes=pq_codes)


def _pad_operands(V: jnp.ndarray, q: jnp.ndarray, plan: BlockedPlan
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Zero-pad to (n_tiles*tile, n_blocks*block).

    Zero coordinate padding rescales every arm's blocked mean by the same
    N/(n_blocks*block) factor — the top-K ranking is unchanged.  Zero arm
    padding is masked out of every top-k via the validity mask.
    """
    n_pad = plan.n_tiles * plan.tile - V.shape[0]
    c_pad = plan.n_blocks * plan.block - V.shape[-1]
    if n_pad or c_pad:
        V = jnp.pad(V, ((0, n_pad), (0, c_pad)))
    if c_pad:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, c_pad)])
    return V, q


def _tile_major(V: jnp.ndarray, plan: BlockedPlan) -> jnp.ndarray:
    """(n_tiles*R, n_blocks*C) -> (n_tiles, n_blocks, R, C)."""
    R, C = plan.tile, plan.block
    return V.reshape(plan.n_tiles, R, plan.n_blocks, C).transpose(0, 2, 1, 3)


def _quantize_table(V4: jnp.ndarray, plan: BlockedPlan) -> Tuple:
    """The plan's table artifacts ``(Vq, vaux)`` (DESIGN.md §10).

    ``(V8, vscale)`` for int8, ``(P4 packed, vscale)`` for int4,
    ``(codes, codebook)`` for pq (codebook trained in-trace — the
    deterministic `pq_train`, so repeated calls agree bit-for-bit).
    Jit-traceable; the decode path skips it when a store hands in its
    incrementally maintained shadow instead.
    """
    if plan.precision == "int8":
        return quantize_tiles(V4)
    if plan.precision == "int4":
        return quantize_tiles_int4(V4)
    if plan.precision == "pq":
        cb = pq_train(V4, n_codes=plan.pq_codes, subdims=plan.pq_subdims)
        return pq_encode(V4, cb), cb
    raise ValueError(f"no table quantizer for precision {plan.precision!r}")


def measured_plan_quant_err(V, *, precision: str, tile: int = 8,
                            block: int = 512, pq_subdims: int = 8,
                            pq_codes: int = 16, n_queries: int = 32,
                            seed: int = 0, safety: float = 2.0) -> float:
    """Calibrate the measured per-pull error bound for a (table, geometry).

    Pads and tiles ``V`` exactly as the cascade will (``block`` is the
    EFFECTIVE pull width — pass ``coord_block`` when calibrating a coord
    plan), builds the tier's quantized artifacts, and returns
    `repro.core.quantize.measured_quant_err` over ``n_queries``
    calibration draws: the ``quant_err=`` value `make_plan` feeds to
    ``make_schedule`` (DESIGN.md §10, measured-vs-worst-case).

    Args:
      V: (n, N) item matrix (host or device array).
      precision: 'int8' | 'int4' | 'pq'.
      safety: multiplicative inflation of the observed max error
        (default 2.0) — the conservativeness margin
        tests/test_guarantees.py checks empirically.
    """
    V = jnp.asarray(V, jnp.float32)
    n, N = V.shape
    block = min(block, N)
    if precision == "int4" and block % 2 != 0:
        raise ValueError(f"precision='int4' needs an even pull width, "
                         f"got block={block}")
    if precision == "pq" and block % pq_subdims != 0:
        raise ValueError(f"precision='pq' needs pull width divisible by "
                         f"pq_subdims, got block={block}, "
                         f"pq_subdims={pq_subdims}")
    # geometry-only fp32 plan: same padding and tiling as the real one
    geo = make_plan(n, N, tile=tile, block=block, precision="fp32")
    Vp, _ = _pad_operands(V, jnp.zeros((N,), jnp.float32), geo)
    V4 = _tile_major(Vp, geo)
    if precision == "int8":
        quant = quantize_tiles(V4)
    elif precision == "int4":
        quant = quantize_tiles_int4(V4)
    elif precision == "pq":
        cb = pq_train(V4, n_codes=pq_codes, subdims=pq_subdims)
        quant = (pq_encode(V4, cb), cb)
    else:
        raise ValueError(f"no measured error model for precision "
                         f"{precision!r} (expected 'int8', 'int4' or 'pq')")
    return measured_quant_err(V4, quant, precision=precision,
                              n_queries=n_queries, seed=seed, safety=safety)


def make_measured_plan(V, K: int = 1, eps: float = 0.1, delta: float = 0.05,
                       value_range: float = 1.0, tile: int = 8,
                       block: int = 512, range_mode: str = "clt",
                       precision: str = "pq", bound: str = "hoeffding",
                       pull_mode: str = "row", coord_block: int = 128,
                       pq_subdims: int = 8, pq_codes: int = 16,
                       n_queries: int = 32, seed: int = 0,
                       safety: float = 2.0) -> BlockedPlan:
    """`make_plan` with a measured (not worst-case) quantization bias.

    Calibrates `measured_plan_quant_err` on ``V`` at the plan's actual
    pull width and passes it as ``quant_err`` — the required entry point
    for ``precision='pq'`` and the tighter-bounds option for
    'int8'/'int4' (DESIGN.md §10).  ``pull_mode='hybrid'`` measures the
    error at EACH candidate width (row pulls see ``block``-wide slices,
    coord pulls ``coord_block``-wide — different codebooks, different
    bias), prices both plans with their own measured bound, and keeps the
    `choose_pull_mode` winner.
    """
    n, N = jnp.asarray(V).shape
    if precision == "fp32":
        raise ValueError("precision='fp32' has no quantization error to "
                         "measure; use make_plan")
    kwargs = dict(K=K, eps=eps, delta=delta, value_range=value_range,
                  tile=tile, block=block, range_mode=range_mode,
                  precision=precision, bound=bound, coord_block=coord_block,
                  pq_subdims=pq_subdims, pq_codes=pq_codes)
    if pull_mode == "hybrid":
        mkwargs = dict(kwargs, n_queries=n_queries, seed=seed, safety=safety)
        row_plan = make_measured_plan(V, pull_mode="row", **mkwargs)
        coord_plan = make_measured_plan(V, pull_mode="coord", **mkwargs)
        winner = choose_pull_mode(row_plan, coord_plan)
        return row_plan if winner == "row" else coord_plan
    width = coord_block if pull_mode == "coord" else block
    qerr = measured_plan_quant_err(V, precision=precision, tile=tile,
                                   block=width, pq_subdims=pq_subdims,
                                   pq_codes=pq_codes, n_queries=n_queries,
                                   seed=seed, safety=safety)
    return make_plan(n, N, pull_mode=pull_mode, quant_err=qerr, **kwargs)


def fused_flat_schedule(plan: BlockedPlan, *, final_exact: bool,
                        adaptive: bool = False) -> FlatSchedule:
    """The flat schedule the fused kernel runs for ``plan``.

    Coverage steps complete the final survivors only on the fp32,
    non-adaptive path; the quantized tiers and adaptive mode rescore
    their candidates in fp32 instead (`_fused_call`).
    """
    return flatten_schedule(
        plan.schedule, final_coverage=(final_exact and not adaptive
                                       and plan.precision == "fp32"))


def fused_pull_group(plan: BlockedPlan, table_dtype) -> int:
    """G: the most survivor tiles one fused-kernel grid step pulls after
    round 1, for ``plan``'s tiles as the kernel reads them (the table's
    own dtype on the fp32 tier, int8 codes, nibble-packed int4 or uint8
    pq codes on the others)."""
    from repro.kernels.fused_cascade import pull_group_size

    Cs, dtype = {"fp32": (plan.block, table_dtype),
                 "int8": (plan.block, jnp.int8),
                 "int4": (plan.block // 2, jnp.int8),
                 "pq": (plan.block // plan.pq_subdims, jnp.uint8),
                 }[plan.precision]
    return pull_group_size(plan.tile, Cs, dtype)


def _fused_call(V4, qb_or_Qb, perm_or_perms, *, plan: BlockedPlan,
                final_exact: bool, batched: bool, k_out: Optional[int] = None,
                n_valid=None, vscale=None, qscale=None, codebook=None,
                adaptive: bool = False):
    """Dispatch the whole cascade as exactly one Pallas kernel launch.

    On the quantized tiers (``vscale``/``qscale`` for int8/int4,
    ``codebook`` for pq) ``final_exact`` never appends coverage steps:
    exactness comes from the caller's fp32 candidate rescore instead of
    in-kernel coverage completion, so the flat schedule stays at the
    sampling pull count (DESIGN.md §10).  The adaptive path (DESIGN.md
    §12) does the same — coverage steps can't be skipped by a mid-flight
    certification, so exactness always comes from the candidate rescore —
    and passes the per-round certification coefficients; the kernel then
    returns a third ``rounds_used`` output.  ``plan.precision='int4'``
    ships the table nibble-packed (last dim C/2) with
    ``packed_int4=True``; the kernel unpacks inside the pull step.
    """
    from repro.kernels import ops as _kops

    flat = fused_flat_schedule(plan, final_exact=final_exact,
                               adaptive=adaptive)
    slotcode, rmeta = flat.packed()
    fn = _kops.fused_cascade_batched if batched else _kops.fused_cascade
    # the dense phase's round-1 columns, then one column per step
    cols = perm_or_perms[..., jnp.asarray(flat.col_pos)]
    cert = (jnp.asarray(cert_coeffs(plan.schedule)) if adaptive else None)
    return fn(V4, qb_or_Qb, slotcode, jnp.asarray(rmeta), cols,
              n_arms=plan.n, K=plan.K, t_final=flat.t_final,
              n_final=flat.n_final, t_dense=flat.t_dense, k_out=k_out,
              n_valid=n_valid,
              vscale=vscale, qscale=qscale, codebook=codebook,
              packed_int4=plan.precision == "int4", cert=cert, k_cert=plan.K,
              track_var=adaptive and plan.schedule.bound == "bernstein")


def _scan_pulls(sums, V4, qb, idx, cols, vscale=None, qscale=None,
                sums2=None, codebook=None, packed_int4=False):
    """One round of pulls as a scan over its coordinate blocks.

    Gathers a single (T, R, C) slab per block — the (T, dt, R, C) gather of
    the pre-fused implementation never exists.  Accumulation order (blocks
    in permutation order) matches the fused kernel's grid order, which is
    what keeps the two paths bitwise-comparable in interpret mode; the
    fp32 block-dot is the kernel's own `f32_tile_dot`.

    With ``vscale``/``qscale`` (int8/int4 operands, DESIGN.md §10) each
    block's tile-dot runs int8 x int8 -> int32 — exact — and is
    dequantized with the same scalar product and the same two float ops
    per entry as the fused kernel's pull step, preserving bitwise parity;
    ``packed_int4`` first sign-extends the nibbles with the SAME
    `unpack_int4` the kernel calls.  With ``codebook`` (pq) the slab
    holds uint8 codes, ``qb`` stays f32, and the block-dot is the shared
    `pq_tile_dot` LUT walk — again literally the kernel's function.

    With ``sums2`` (the adaptive 'bernstein' path, DESIGN.md §12) a
    running sum of squared block-dots rides along — the same ``part *
    part`` elementwise product the kernel accumulates — and the function
    returns ``(sums, sums2)`` instead of ``sums``.
    """
    quantized = vscale is not None
    track = sums2 is not None

    def body(carry, col):
        s = carry[0] if track else carry
        if codebook is not None:
            part = pq_tile_dot(V4[idx, col], qb[col], codebook[col])
        elif quantized:
            slab = V4[idx, col]
            if packed_int4:
                slab = unpack_int4(slab)
            raw = jnp.einsum("trc,c->tr", slab, qb[col],
                             preferred_element_type=jnp.int32)
            scl = vscale[idx, col] * qscale[col]            # (T,)
            part = raw.astype(jnp.float32) * scl[:, None]
        else:
            part = f32_tile_dot(V4[idx, col], qb[col])
        if track:
            return (s + part, carry[1] + part * part), None
        return s + part, None

    out, _ = jax.lax.scan(body, (sums, sums2) if track else sums, cols)
    return out


def _cert_fire(mu, rad, valid, K):
    """Certification predicate of the adaptive early exit (DESIGN.md §12).

    ``mu``/``rad``/``valid``: (..., T, R) post-elimination survivor means,
    radii and validity masks.  Fires (True) when the top-``K``-by-mean
    valid rows' lower bounds ``mu - rad`` clear every other valid row's
    upper bound ``mu + rad`` — on the confidence event those K rows' true
    means then dominate every other survivor's, so the eventual top-K
    extraction is already certified (suboptimality 0 <= eps).  With fewer
    than K valid rows the comparison set is empty and the predicate fires
    trivially (`-inf >= -inf`).  Row enumeration order matches the
    kernel's slot-major certification buffers, so tie-breaks agree
    bitwise.
    """
    neg = jnp.float32(-jnp.inf)
    lead = mu.shape[:-2]
    bufM = jnp.where(valid, mu, neg).reshape(*lead, -1)
    bufU = jnp.where(valid, mu + rad, neg).reshape(*lead, -1)
    bufL = jnp.where(valid, mu - rad, neg).reshape(*lead, -1)
    _, pos = jax.lax.top_k(bufM, K)
    minlb = jnp.min(jnp.take_along_axis(bufL, pos, axis=-1), axis=-1)
    if lead:
        bufU = bufU.at[jnp.arange(lead[0])[:, None], pos].set(neg)
    else:
        bufU = bufU.at[pos].set(neg)
    return minlb >= jnp.max(bufU, axis=-1)


def _cert_update(mu, v, valid, cert, l, t_cum, K, active, rounds_used,
                 t_stop):
    """One round-boundary certification step of the jnp fallbacks.

    Evaluates the per-row radius ``a_l sqrt(max(v, 0)) + b_l`` (``v`` is
    None on the variance-free 'hoeffding' family), runs `_cert_fire` over
    the post-elimination survivors, and advances the per-query
    ``(active, rounds_used, t_stop)`` state — the same bookkeeping the
    fused kernel's ``_certify`` block performs in SMEM.  Shared by
    `_run_blocked` (scalar state) and `_run_decode` ((B,) state); the ops
    are rank-polymorphic, which keeps both paths bitwise-identical to the
    kernel.
    """
    if v is not None:
        rad = (jnp.float32(cert[l, 0]) * jnp.sqrt(jnp.maximum(v, 0.0))
               + jnp.float32(cert[l, 1]))
    else:
        rad = jnp.full_like(mu, jnp.float32(cert[l, 1]))
    fire = _cert_fire(mu, rad, valid, K)
    fire_now = jnp.logical_and(active, fire)
    rounds_used = jnp.where(fire_now, l + 1, rounds_used)
    t_stop = jnp.where(fire_now, t_cum, t_stop)
    active = jnp.logical_and(active, jnp.logical_not(fire))
    return active, rounds_used, t_stop


def _rescore_rows(Vp, Qp, ids, n_valid, *, plan: BlockedPlan, batched: bool):
    """fp32-exact rescore + descending re-sort of cascade candidates (§10).

    ``Vp``/``Qp`` are the zero-padded operands, so each gathered row's
    inner product equals the unpadded one and dividing by the true ``N``
    lands directly on (q . v)/N — no padding rescale needed.  Rows at or
    past ``n_valid`` (tile/caller padding the masked extraction may emit
    as filler) are pinned to -inf so they can never re-enter the top-K.
    """
    neg = jnp.float32(-jnp.inf)
    safe = jnp.clip(ids, 0, Vp.shape[0] - 1)
    hi = jax.lax.Precision.HIGHEST     # TPU's default f32 dot is bf16-pass
    if batched:
        scores = jnp.einsum("bkc,bc->bk", Vp[safe], Qp,
                            preferred_element_type=jnp.float32,
                            precision=hi)
    else:
        scores = jnp.dot(Vp[safe], Qp, preferred_element_type=jnp.float32,
                         precision=hi)
    scores = jnp.where(ids < n_valid, scores / jnp.float32(plan.N), neg)
    vals, pos = jax.lax.top_k(scores, ids.shape[-1])
    ids = (jnp.take_along_axis(ids, pos, axis=-1) if batched
           else ids[pos])
    return ids, vals


@functools.partial(jax.jit, static_argnames=("plan", "final_exact",
                                             "use_pallas", "adaptive"))
def _run_blocked(V: jnp.ndarray, q: jnp.ndarray, key: jax.Array, *,
                 plan: BlockedPlan, final_exact: bool = False,
                 use_pallas: bool = False,
                 adaptive: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (topk_ids (K,), topk_scores (K,)) — scores are mean products.

    With ``adaptive`` a third output ``rounds_used`` (int32 scalar) rides
    along and pulls freeze at the first certified round (DESIGN.md §12).
    """
    R, C = plan.tile, plan.block
    V, q = _pad_operands(jnp.asarray(V), jnp.asarray(q), plan)
    V4 = _tile_major(V, plan)
    qb = q.reshape(plan.n_blocks, C)
    perm = jax.random.permutation(key, plan.n_blocks)
    # undo the zero-padding rescale so scores estimate (q . v)/N
    scale = (plan.n_blocks * C) / plan.N
    quantized = plan.precision != "fp32"
    track_var = adaptive and plan.schedule.bound == "bernstein"
    if quantized:
        Vq, vaux = _quantize_table(V4, plan)
        if plan.precision == "pq":          # pq queries stay f32 (LUT walk)
            q_in, vscale, qscale, codebook = qb, None, None, vaux
        else:
            q_in, qscale = quantize_blocks(qb)
            vscale, codebook = vaux, None

    if use_pallas:
        rounds_used = None
        if quantized:
            out = _fused_call(Vq, q_in, perm, plan=plan,
                              final_exact=final_exact, batched=False,
                              vscale=vscale, qscale=qscale,
                              codebook=codebook, adaptive=adaptive)
        else:
            out = _fused_call(V4, qb, perm, plan=plan,
                              final_exact=final_exact, batched=False,
                              adaptive=adaptive)
        if adaptive:
            ids, vals, rounds_used = out
        else:
            ids, vals = out
        if final_exact and (quantized or adaptive):
            ids, vals = _rescore_rows(V, q, ids, plan.n, plan=plan,
                                      batched=False)
        else:
            vals = vals * jnp.float32(scale)
        return (ids, vals, rounds_used) if adaptive else (ids, vals)

    arm_ids0 = jnp.arange(plan.n_tiles * R).reshape(plan.n_tiles, R)
    valid0 = (arm_ids0 < plan.n).astype(jnp.float32)

    idx = jnp.arange(plan.n_tiles)
    sums = jnp.zeros((plan.n_tiles, R), dtype=jnp.float32)
    sums2 = jnp.zeros_like(sums) if track_var else None
    t_prev = 0
    neg = jnp.asarray(-jnp.inf, dtype=jnp.float32)
    n_rounds = len(plan.schedule.rounds)
    if adaptive:
        cert = cert_coeffs(plan.schedule)
        t_last = plan.schedule.rounds[-1].t_cum if n_rounds else 0
        active = jnp.asarray(True)
        t_stop = jnp.asarray(t_last, jnp.int32)
        rounds_used = jnp.asarray(n_rounds, jnp.int32)

    for l, rnd in enumerate(plan.schedule.rounds):
        if rnd.t_new > 0:
            cols = jax.lax.slice_in_dim(perm, t_prev, rnd.t_cum)  # static
            if quantized:
                new = _scan_pulls(sums, Vq, q_in, idx, cols, vscale, qscale,
                                  sums2=sums2, codebook=codebook,
                                  packed_int4=plan.precision == "int4")
            else:
                new = _scan_pulls(sums, V4, qb, idx, cols, sums2=sums2)
            if track_var:
                new, new2 = new
                sums2 = jnp.where(active, new2, sums2)
            if adaptive:   # a certified query's accumulator stays frozen
                sums = jnp.where(active, new, sums)
            else:
                sums = new
        t_prev = rnd.t_cum
        denom = jnp.float32(t_prev * C)
        means = sums / denom
        valid = valid0[idx]
        tile_score = jnp.where(valid > 0, means, neg).max(axis=1)
        _, keep = jax.lax.top_k(tile_score, rnd.n_keep)            # static
        idx, sums = idx[keep], sums[keep]
        if track_var:
            sums2 = sums2[keep]
        if adaptive:
            mu = sums / denom
            v = (sums2 / (denom * jnp.float32(C)) - mu * mu
                 if track_var else None)
            active, rounds_used, t_stop = _cert_update(
                mu, v, valid0[idx] > 0, cert, l, rnd.t_cum, plan.K,
                active, rounds_used, t_stop)

    valid = valid0[idx]
    if final_exact and not quantized and not adaptive:
        # exact rescore of the few survivors: (T_f*R, N') x (N',); divide by
        # the padded width N' = n_blocks*C so the caller-side rescale by
        # N'/N lands on (q . v)/N (dividing by N here double-counted the
        # rescale whenever N % block != 0)
        Vfin = V4[idx].transpose(0, 2, 1, 3).reshape(idx.shape[0] * R, -1)
        scores = (Vfin @ q).astype(jnp.float32) / jnp.float32(
            plan.n_blocks * C)
        scores = scores.reshape(idx.shape[0], R)
    elif adaptive:
        # normalize by the query's ACTUAL pull count (frozen at t_stop)
        scores = sums / (jnp.maximum(t_stop, 1) * C).astype(jnp.float32)
    else:
        # int8 + final_exact rescoring happens on the candidates below —
        # coverage completion in int8 would still carry quantization bias
        scores = sums / jnp.float32(max(1, t_prev) * C)
    flat = jnp.where(valid > 0, scores, neg).reshape(-1)
    top_vals, top_pos = jax.lax.top_k(flat, plan.K)
    arm_ids = arm_ids0[idx].reshape(-1)[top_pos]
    if final_exact and (quantized or adaptive):
        arm_ids, top_vals = _rescore_rows(V, q, arm_ids, plan.n, plan=plan,
                                          batched=False)
    else:
        top_vals = top_vals * jnp.float32(scale)
    return (arm_ids, top_vals, rounds_used) if adaptive else (arm_ids,
                                                              top_vals)


def bounded_me_blocked(V, q, key, *, K: int = 1, eps: float = 0.1,
                       delta: float = 0.05, value_range: float = 1.0,
                       tile: int = 8, block: int = 512,
                       final_exact: bool = False, use_pallas: bool = False,
                       precision: str = "fp32", adaptive: bool = False,
                       bound: str = "hoeffding",
                       pull_mode: str = "row", coord_block: int = 128,
                       quant_err: Optional[float] = None,
                       pq_subdims: int = 8, pq_codes: int = 16,
                       plan: Optional[BlockedPlan] = None):
    """Top-K MIPS over rows of ``V`` for query ``q`` (single query).

    Returns ``(ids (K,), scores (K,), plan)`` where scores estimate
    ``(q . v)/N``.  All shapes are static; safe under jit/pjit.  With
    ``use_pallas=True`` the entire cascade is one kernel dispatch.
    ``precision='int8'``/``'int4'`` sample on a scalar integer grid under
    quantization-widened bounds; ``'pq'`` samples product-quantized codes
    — with ``quant_err=None`` the pq plan is auto-calibrated on ``V`` via
    `make_measured_plan` (DESIGN.md §10).  ``final_exact`` then rescores
    the winners in fp32 on every quantized tier.
    ``adaptive=True`` certifies early exit at round boundaries under the
    plan's ``bound`` radius family and returns a 4-tuple
    ``(ids, scores, rounds_used, plan)`` (DESIGN.md §12);
    ``adaptive=False`` is bit-identical to not passing it.
    ``pull_mode`` selects the reward stream — 'row', 'coord' (narrow
    ``coord_block``-wide feature tiles, DESIGN.md §14) or 'hybrid'
    (cheaper of the two by `choose_pull_mode`).  When ``plan`` is given
    its own precision/bound/pull_mode win.
    """
    n, N = V.shape
    if plan is None:
        kwargs = dict(K=K, eps=eps, delta=delta, value_range=value_range,
                      tile=tile, block=block, precision=precision,
                      bound=bound, pull_mode=pull_mode,
                      coord_block=coord_block, pq_subdims=pq_subdims,
                      pq_codes=pq_codes)
        if precision == "pq" and quant_err is None:
            plan = make_measured_plan(V, **kwargs)
        else:
            plan = make_plan(n, N, quant_err=quant_err, **kwargs)
    out = _run_blocked(jnp.asarray(V), jnp.asarray(q), key, plan=plan,
                       final_exact=final_exact, use_pallas=use_pallas,
                       adaptive=adaptive)
    return (*out, plan)


@functools.partial(jax.jit, static_argnames=("plan", "final_exact",
                                             "adaptive"))
def _run_batched_fused(V, Q, keys, *, plan: BlockedPlan, final_exact: bool,
                       adaptive: bool = False):
    """Per-query-key batch as ONE batched kernel dispatch (B in the grid)."""
    C = plan.block
    B = Q.shape[0]
    V, Q = _pad_operands(jnp.asarray(V), jnp.asarray(Q), plan)
    V4 = _tile_major(V, plan)
    Qb = Q.reshape(B, plan.n_blocks, C)
    perms = jax.vmap(
        lambda k: jax.random.permutation(k, plan.n_blocks))(keys)
    scale = (plan.n_blocks * C) / plan.N
    rounds_used = None
    if plan.precision != "fp32":
        Vq, vaux = _quantize_table(V4, plan)
        if plan.precision == "pq":
            Q_in, vscale, qscale, codebook = Qb, None, None, vaux
        else:
            Q_in, qscale = quantize_blocks(Qb)
            vscale, codebook = vaux, None
        out = _fused_call(Vq, Q_in, perms, plan=plan,
                          final_exact=final_exact, batched=True,
                          vscale=vscale, qscale=qscale, codebook=codebook,
                          adaptive=adaptive)
    else:
        out = _fused_call(V4, Qb, perms, plan=plan,
                          final_exact=final_exact, batched=True,
                          adaptive=adaptive)
    if adaptive:
        ids, vals, rounds_used = out
    else:
        ids, vals = out
    if final_exact and (plan.precision != "fp32" or adaptive):
        ids, vals = _rescore_rows(V, Q, ids, plan.n, plan=plan, batched=True)
    else:
        vals = vals * jnp.float32(scale)
    return (ids, vals, rounds_used) if adaptive else (ids, vals)


def bounded_me_batched(V, Q, keys, *, plan: BlockedPlan,
                       final_exact: bool = False, use_pallas: bool = False,
                       adaptive: bool = False):
    """BoundedME over a batch of queries ``Q`` (B, N) with per-query keys.

    Results match a loop of single-query calls with the same keys.  With
    ``use_pallas=True`` the whole batch is ONE batched fused-kernel dispatch
    (query axis in the grid); otherwise the scan fallback is vmapped.  For
    the decode serving hot path prefer `bounded_me_decode`, which shares the
    block permutation across the batch so early rounds become dense MXU
    tile-matmuls even without Pallas.  ``adaptive=True`` appends a
    per-query ``rounds_used (B,)`` output (DESIGN.md §12).
    """
    if use_pallas:
        return _run_batched_fused(jnp.asarray(V), jnp.asarray(Q), keys,
                                  plan=plan, final_exact=final_exact,
                                  adaptive=adaptive)
    fn = functools.partial(_run_blocked, plan=plan, final_exact=final_exact,
                           use_pallas=False, adaptive=adaptive)
    return jax.vmap(fn, in_axes=(None, 0, 0))(jnp.asarray(V), jnp.asarray(Q),
                                              keys)


@functools.partial(jax.jit, static_argnames=("plan", "final_exact",
                                             "use_pallas", "k_out",
                                             "adaptive"))
def _run_decode(V, Q, key, n_valid, Vq=None, vaux=None, *,
                plan: BlockedPlan, final_exact: bool,
                use_pallas: bool, k_out: int, adaptive: bool = False):
    R, C = plan.tile, plan.block
    B = Q.shape[0]
    V, Q = _pad_operands(jnp.asarray(V), jnp.asarray(Q), plan)
    V4 = _tile_major(V, plan)
    Qb = Q.reshape(B, plan.n_blocks, C)
    # ONE permutation shared by the whole batch: identical pull columns per
    # round let round pulls fuse into (n_tiles*R, C) x (C, B) MXU matmuls
    # (marginally each query still samples uniformly without replacement)
    perm = jax.random.permutation(key, plan.n_blocks)
    scale = (plan.n_blocks * C) / plan.N
    quantized = plan.precision != "fp32"
    is_pq = plan.precision == "pq"
    packed4 = plan.precision == "int4"
    track_var = adaptive and plan.schedule.bound == "bernstein"
    if quantized:
        if Vq is None:
            Vq, vaux = _quantize_table(V4, plan)
        if is_pq:                    # pq queries stay f32 (LUT walk)
            vscale, qscale, codebook, Q8 = None, None, vaux, None
        else:
            Q8, qscale = quantize_blocks(Qb)     # per query: (B, n_blocks)
            vscale, codebook = vaux, None

    if use_pallas:
        rounds_used = None
        # one (S,) column list serves the whole batch (SMEM: 1 word/step)
        if quantized:
            out = _fused_call(Vq, Qb if is_pq else Q8, perm, plan=plan,
                              final_exact=final_exact, batched=True,
                              k_out=k_out, n_valid=n_valid,
                              vscale=vscale, qscale=qscale,
                              codebook=codebook, adaptive=adaptive)
        else:
            out = _fused_call(V4, Qb, perm, plan=plan,
                              final_exact=final_exact, batched=True,
                              k_out=k_out, n_valid=n_valid,
                              adaptive=adaptive)
        if adaptive:
            ids, vals, rounds_used = out
        else:
            ids, vals = out
        if final_exact and (quantized or adaptive):
            ids, vals = _rescore_rows(V, Q, ids, n_valid, plan=plan,
                                      batched=True)
        else:
            vals = vals * jnp.float32(scale)
        return (ids, vals, rounds_used) if adaptive else (ids, vals)

    arm_ids0 = jnp.arange(plan.n_tiles * R).reshape(plan.n_tiles, R)
    valid0 = (arm_ids0 < n_valid).astype(jnp.float32)
    brange = jnp.arange(B)[:, None]

    idx = jnp.broadcast_to(jnp.arange(plan.n_tiles), (B, plan.n_tiles))
    sums = jnp.zeros((B, plan.n_tiles, R), dtype=jnp.float32)
    sums2 = jnp.zeros_like(sums) if track_var else None
    t_prev = 0
    neg = jnp.asarray(-jnp.inf, dtype=jnp.float32)
    n_rounds = len(plan.schedule.rounds)
    if adaptive:
        cert = cert_coeffs(plan.schedule)
        t_last = plan.schedule.rounds[-1].t_cum if n_rounds else 0
        active = jnp.ones((B,), bool)
        t_stop = jnp.full((B,), t_last, jnp.int32)
        rounds_used = jnp.full((B,), n_rounds, jnp.int32)
        gate = lambda new, old: jnp.where(active[:, None, None], new, old)

    for l, rnd in enumerate(plan.schedule.rounds):
        if rnd.t_new > 0:
            cols = jax.lax.slice_in_dim(perm, t_prev, rnd.t_cum)   # (dt,)
            Qsrc = Q8 if (quantized and not is_pq) else Qb
            qsel = jnp.moveaxis(Qsrc[:, cols], 0, 1)               # (dt,B,C)
            if B * rnd.n_arms >= plan.n_tiles:
                # early rounds: survivor union ~ every tile, so a dense
                # (n_tiles*R, C) x (C, B) tile-matmul per block beats any
                # gather; eliminated tiles accumulate garbage that is never
                # read back (survivor gathers go through `idx`)
                if is_pq:
                    def dense(s, xs):
                        col, qcol = xs           # qcol: (B, C) f32
                        # vmap of the SHARED per-query LUT walk keeps the
                        # per-slice arithmetic identical to the kernel's
                        part = jax.vmap(
                            lambda qq: pq_tile_dot(Vq[:, col], qq,
                                                   codebook[col]))(qcol)
                        if track_var:
                            return ((s[0] + part, s[1] + part * part),
                                    None)
                        return s + part, None
                elif quantized:
                    def dense(s, xs):
                        col, qcol = xs
                        slab = Vq[:, col]
                        if packed4:
                            slab = unpack_int4(slab)
                        raw = jnp.einsum("trc,bc->btr", slab, qcol,
                                         preferred_element_type=jnp.int32)
                        scl = (vscale[:, col][None, :, None]
                               * qscale[:, col][:, None, None])  # (B, T, 1)
                        part = raw.astype(jnp.float32) * scl
                        if track_var:
                            return ((s[0] + part, s[1] + part * part),
                                    None)
                        return s + part, None
                else:
                    def dense(s, xs):
                        col, qcol = xs
                        part = f32_tile_dot(V4[:, col][None],
                                            qcol[:, None])      # (B, T, R)
                        if track_var:
                            return ((s[0] + part, s[1] + part * part),
                                    None)
                        return s + part, None
                carry = (sums, sums2) if track_var else sums
                new, _ = jax.lax.scan(dense, carry, (cols, qsel))
                if track_var:
                    new, new2 = new
                    sums2 = gate(new2, sums2)
                if adaptive:   # certified queries' accumulators stay frozen
                    sums = gate(new, sums)
                else:
                    sums = new
            else:
                # late rounds: few survivors per query — per-query gather
                # scans, sequential over the batch to bound the working
                # set.  Each scan starts from the survivors' running sums
                # and adds this round's blocks in order, as the kernel
                # does, so the sums round exactly as the kernel's
                int_tier = quantized and not is_pq

                def one(args):
                    idx_i, q_i, s_i, *rest = args
                    qs_i = rest.pop(0) if int_tier else None
                    s20 = rest[0][idx_i] if track_var else None
                    if is_pq:
                        return _scan_pulls(s_i[idx_i], Vq, q_i, idx_i, cols,
                                           sums2=s20, codebook=codebook)
                    if int_tier:
                        return _scan_pulls(s_i[idx_i], Vq, q_i, idx_i, cols,
                                           vscale, qs_i, sums2=s20,
                                           packed_int4=packed4)
                    return _scan_pulls(s_i[idx_i], V4, q_i, idx_i, cols,
                                       sums2=s20)
                xs = ((idx, Q8 if int_tier else Qb, sums)
                      + ((qscale,) if int_tier else ())
                      + ((sums2,) if track_var else ()))
                parts = jax.lax.map(one, xs)                  # (B, T, R)
                if track_var:
                    parts, parts2 = parts
                    sums2 = gate(sums2.at[brange, idx].set(parts2), sums2)
                if adaptive:
                    sums = gate(sums.at[brange, idx].set(parts), sums)
                else:
                    sums = sums.at[brange, idx].set(parts)
        t_prev = rnd.t_cum
        denom = jnp.float32(t_prev * C)
        means = jnp.take_along_axis(sums, idx[..., None], axis=1)
        means = means / denom
        valid = valid0[idx]
        tile_score = jnp.where(valid > 0, means, neg).max(axis=-1)  # (B, T)
        _, keep = jax.lax.top_k(tile_score, rnd.n_keep)
        idx = jnp.take_along_axis(idx, keep, axis=1)
        if adaptive:
            mu = jnp.take_along_axis(sums, idx[..., None], axis=1) / denom
            v = (jnp.take_along_axis(sums2, idx[..., None], axis=1)
                 / (denom * jnp.float32(C)) - mu * mu
                 if track_var else None)
            active, rounds_used, t_stop = _cert_update(
                mu, v, valid0[idx] > 0, cert, l, rnd.t_cum, plan.K,
                active, rounds_used, t_stop)

    valid = valid0[idx]
    if final_exact and not quantized and not adaptive:
        Vfin = V4[idx]                                 # (B, Tf, nb, R, C)
        scores = jnp.einsum("btnrc,bnc->btr", Vfin, Qb,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.float32(plan.n_blocks * C)
    elif adaptive:
        # normalize by each query's ACTUAL pull count (frozen at t_stop)
        scores = jnp.take_along_axis(sums, idx[..., None], axis=1)
        scores = scores / (jnp.maximum(t_stop, 1)[:, None, None]
                           * C).astype(jnp.float32)
    else:
        # the int8 + final_exact rescore runs on the k_out candidates below
        scores = jnp.take_along_axis(sums, idx[..., None], axis=1)
        scores = scores / jnp.float32(max(1, t_prev) * C)
    flat = jnp.where(valid > 0, scores, neg).reshape(B, -1)
    top_vals, top_pos = jax.lax.top_k(flat, k_out)
    arm_ids = jnp.take_along_axis(arm_ids0[idx].reshape(B, -1), top_pos,
                                  axis=1)
    if final_exact and (quantized or adaptive):
        arm_ids, top_vals = _rescore_rows(V, Q, arm_ids, n_valid, plan=plan,
                                          batched=True)
    else:
        top_vals = top_vals * jnp.float32(scale)
    return (arm_ids, top_vals, rounds_used) if adaptive else (arm_ids,
                                                              top_vals)


def bounded_me_decode(V, Q, key, *, plan: BlockedPlan,
                      final_exact: bool = True,
                      use_pallas: Optional[bool] = None,
                      k_out: Optional[int] = None,
                      n_valid=None, quantized=None,
                      adaptive: bool = False):
    """Batched-decode BoundedME: one dispatch for a whole (B, N) batch.

    The serving hot path (DESIGN.md §3).  All queries share one block
    permutation so every round's pulls are identical columns across the
    batch: with ``use_pallas`` the batched fused kernel serves the batch in
    a single `pallas_call`; the jnp fallback turns early rounds into dense
    (n_tiles*R, C) x (C, B) MXU tile-matmuls instead of the per-query
    gather einsum the vmapped path pays.  Survivor sets and eliminations
    stay fully per-query.

    Args:
      V: (n, N) item/arm matrix (rows are arms); any float dtype.
      Q: (B, N) query batch, same trailing dim as ``V``.
      key: PRNG key for the shared block permutation.
      plan: static :class:`BlockedPlan` from :func:`make_plan` — carries the
        (eps, delta) calibration and the sampling ``precision``; must match
        ``V``'s (n, N).  With ``plan.precision='int8'`` every sampling
        round pulls int8 tiles under quantization-widened confidence
        bounds (DESIGN.md §10).
      final_exact: make the returned scores exact mean products (q . v)/N
        instead of block-mean estimates — via in-cascade coverage
        completion at fp32, or via an fp32 rescore of the ``k_out``
        candidates on the int8 path (which never pays coverage pulls).
      use_pallas: force/deny the fused kernel (default: auto, TPU only).
      k_out: how many candidates to return per query (default ``plan.K``).
        The cascade still targets ``plan.K`` (the elimination keeps
        ``plan.k_tiles`` tiles); ``k_out`` only widens the final extraction
        so shard-local callers get a threshold candidate for bound gaps.
        Must satisfy ``plan.K <= k_out <= plan.k_out_cap``.
      n_valid: rows >= n_valid are masked out of every ranking *inside*
        the cascade (default ``plan.n``): caller-padding rows (padded
        vocab, ragged shard) and a dynamic store's dead suffix
        (DESIGN.md §11) can then never occupy survivor or candidate
        slots.  Accepts a traced scalar (per-shard under shard_map, or a
        live-row count that changes between calls without recompiling).
      quantized: optional pre-quantized table operands matching the
        plan's tier — ``(V8, vscale)`` for int8
        (`repro.core.quantize.quantize_tiles` layout), ``(P4, vscale)``
        nibble-packed for int4 (`quantize_tiles_int4`), ``(codes,
        codebook)`` for pq (`pq_encode`/`pq_train`).  When given, the
        in-jit table quantization (and pq codebook training) is skipped —
        this is how a `DynamicTableStore`'s incrementally re-encoded
        shadow reaches the kernel; results are bit-identical to
        quantizing ``V`` in-jit because per-(tile, block) cells (and pq
        code assignments against a frozen codebook) are computed
        independently.  Queries are always quantized in-jit on the
        int8/int4 tiers (they arrive per request); pq queries stay f32.

      adaptive: certify early exit per query at round boundaries under the
        plan's ``bound`` radius family (DESIGN.md §12): a certified
        query's remaining pulls become masked no-ops and a third output
        reports its ``rounds_used``.  ``adaptive=False`` (default) is
        bit-identical to the pre-adaptive decode path.  On the int8 path
        certification radii carry the schedule's ``quant_err`` bias — the
        *eps_effective* calibration — so quantization error is still
        absorbed.

    Returns:
      ``(ids (B, k_out) int32, scores (B, k_out) f32)`` sorted by descending
      score.  Entries past the number of real arms (if ``n < k_out``) carry
      ``-inf`` scores and padding ids.  With ``adaptive=True`` a third
      element ``rounds_used (B,) int32`` is appended — the per-query count
      of elimination rounds that actually pulled (the histogram input for
      `benchmarks/bench_adaptive` and the serve engine's stats).
    """
    if use_pallas is None:
        from repro.kernels import ops as _kops
        use_pallas = _kops.on_tpu()
    if k_out is None:
        k_out = plan.K
    if not plan.K <= k_out <= plan.k_out_cap:
        raise ValueError(f"k_out={k_out} outside [K={plan.K}, "
                         f"k_out_cap={plan.k_out_cap}]")
    if n_valid is None:
        n_valid = plan.n
    if quantized is not None and plan.precision == "fp32":
        raise ValueError("pre-quantized operands need a quantized plan "
                         "(precision 'int8', 'int4' or 'pq')")
    Vq, vaux = quantized if quantized is not None else (None, None)
    return _run_decode(jnp.asarray(V), jnp.asarray(Q), key,
                       jnp.asarray(n_valid, jnp.int32), Vq, vaux,
                       plan=plan, final_exact=final_exact,
                       use_pallas=use_pallas, k_out=k_out,
                       adaptive=adaptive)
