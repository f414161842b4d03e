"""Pallas TPU kernel: the whole BoundedME cascade in ONE dispatch.

The per-round `gather_block_dot` kernel still pays one launch + an XLA-level
top-k + survivor reshuffle per elimination round; at decode batch sizes that
dispatch overhead eats the sample-complexity savings the schedule buys.
Because the round structure is data-independent (`repro.core.schedule`), the
*entire* multi-round pull program can be flattened host-side
(`flatten_schedule`) and executed as a single grid (DESIGN.md §3):

  * round 1 starts from every tile, so it needs no survivor state: a
    dense phase pulls its ``t_dense`` permuted column blocks once for the
    whole batch, ``T_blk`` tiles of one column per step (about 1 MiB a
    DMA), and adds every query's share to that query's round-1 sums;
  * after round 1 the (n_tiles, R) f32 accumulator stays VMEM-resident
    and the survivor index set SMEM-resident across all rounds — neither
    round-trips to HBM;
  * each later grid step pulls a group of up to ``G`` surviving (R, C)
    tiles of one column of one round (`repro.core.schedule.group_steps`):
    the previous step starts all of the group's tile DMAs into one half of
    a ``(2, G, R, Cs)`` VMEM ring, on one semaphore, and the step waits on
    each of them, then adds each tile's part to its own accumulator row.
    ``G`` comes from the stored tile (`pull_group_size`: 16 tiles of (8,
    512) at fp32, bf16 or int8).  Only the bytes the bandit pulls ever
    cross the memory bus, and the survivor indices live in SMEM, so the
    "gather" costs no HBM traffic at all;
  * at round boundaries the tile elimination runs *inside* the kernel: the
    survivors' masked tile-max means are written to SMEM in slot order and
    stably sorted descending there (a bottom-up merge sort — exactly
    `lax.top_k`'s order, lowest slot first on ties); the first ``n_keep``
    entries are the next round's survivor list;
  * the final top-K arms are extracted in-kernel and returned as (ids,
    scores) — dispatch count per query drops from O(rounds) to 1.

The grid is flat, ``G1 + B * S`` steps, and one launch serves a (B, N)
decode batch.  The first ``G1`` steps are the dense phase: with one column
list for the batch (``cols (t_dense + S,)``, the decode path) each step
scores all B queries, ``G1 = ceil(n_tiles / T_blk) * t_dense``; with a
list per query (``cols (B, t_dense + S)``) each scores one query and
``G1`` is B times that.  Query ``b``'s own steps follow, ``G1 + b * S``
to ``G1 + (b + 1) * S - 1``, where ``S`` counts the grouped steps: the
first one re-initializes its survivor state, reads its round-1 sums back
and ends round 1 (elimination, then certification when adaptive) without
a pull; each later one pulls a group of survivor tiles or, in a round
that pulls nothing, only eliminates.  VMEM holds one query's accumulator
at a time, so the dense phase stages each finished tile group's ``(B,
T_blk, R)`` sums (the first R of 128 lanes) to an HBM buffer (an extra
``pl.ANY`` output the wrapper drops) and each query's first step DMAs its
slice back.  A schedule with no rounds has no dense phase
and its sums start at zero.  The single-query entry point is the same
kernel at B = 1.

The dense phase and a group step do the same arithmetic as the
fallbacks' pulls, so results stay bitwise equal to the jnp fallbacks in
interpret mode: per (query, tile, row) the fp32 parts are `f32_tile_dot`
(lane-dense over `_dense_chunk` tiles a pass when C > 128) and int8/int4
parts the same exact integer dot dequantized with ``vscale * qscale``,
added from 0.0 in permutation order; adaptive mode also sums ``part *
part``.  A group's tiles are distinct, so each tile still sums its columns
in order, and elimination and certification still run only at a round's
last step.

Mosaic memory rules shape the layout: scalars are only loaded and stored in
SMEM, so every per-step scalar (survivor ids, sort keys, round cursor,
adaptive lanes, the (ids, vals, rounds_used) outputs) lives there, and VMEM
holds only vectors — the accumulator, the pull ring, the query
block, the certification/extraction row buffers.  Reductions over those
buffers run in row chunks so the compiled loop body stays small.  VMEM and
SMEM budgets are computed from the shapes before lowering, and a shape
that cannot fit one TensorCore is refused with a `ValueError`.

Scalar-prefetch operands (SMEM, 1-D), after the entry points group the
caller's per-step list (host data) with `group_steps`:
  slotcode (S,)           packed first slot | PULL_BIT | END_BIT per
                          grouped step; a group's size is
                          ``min(G, n_surv - slot)``, ``n_surv`` from
                          ``rounds_meta`` (``n_final`` after the last
                          round, for the final-coverage steps)
  rounds_meta (3(r+1),)   (t_cum, n_surv, n_keep) per round, consumed at
                          end steps
  cert (2(r+1),)          adaptive only: per-round certification-radius
                          coefficients (a_l, b_l) from
                          `repro.core.schedule.cert_coeffs` (DESIGN.md §12)
  cols (t_dense+S,) or    column-block ids: round 1's ``t_dense`` dense
       (B*(t_dense+S),)   columns, then one per grouped step (the
                          caller's ``perm[flat.col_pos]``, kept at each
                          group's first step); the first form when the
                          batch shares one permutation
  nvalid (1,)             rows >= nvalid are masked out of every ranking
                          (tile padding AND caller padding, e.g. a padded
                          vocab or a ragged shard — DESIGN.md §7); may be
                          a traced value (per-shard under shard_map)

Quantized sampling (DESIGN.md §10): when ``V4``/``qb`` are int8 the caller
passes the per-tile table scales ``vscale (n_tiles, n_blocks) f32`` and the
per-block query scales ``qscale (1|B, n_blocks) f32``
(`repro.core.quantize`).  Each pull's tile-dot then runs int8 x int8 ->
int32 on the MXU — a quarter of the fp32 HBM bytes per pulled tile — and is
dequantized with ``vscale[tile, col] * qscale[col]`` before entering the
same f32 accumulator; elimination, survivor bookkeeping and extraction are
unchanged.  ``vscale`` is held transposed and lane-dense in VMEM, ``qscale``
in SMEM.  The widened confidence radii that absorb the quantization bias
live in the schedule, not here (`make_schedule(quant_err=...)`).

Two further tiers ride the same pull pipeline (DESIGN.md §10), in interpret
mode only — the Mosaic lowering refuses them with `NotImplementedError`:

  * ``packed_int4=True`` — ``V4``'s last dim holds nibble-packed int4
    codes (C/2 bytes per row per pull, half the int8 traffic); the pull
    step sign-extends with the shared `repro.core.quantize.unpack_int4`
    and then runs the SAME int8-style exact integer dot + dequantize.
    Queries stay int8 (W4A8), so ``vscale``/``qscale`` are required.
  * ``codebook`` given — product-quantized tiles: ``V4`` holds uint8
    codes (n_tiles, n_blocks, R, S) with S = C / subdims bytes per row
    per pull, the f32 ``codebook (n_blocks, S, n_codes, subdims)`` sits
    fully VMEM-resident, queries stay f32, and each pull is the shared
    `repro.core.quantize.pq_tile_dot` (bit-exact with the jnp fallbacks
    that call the same function).

In every tier the *stored* last dim of ``V4`` (C, C/2 or S) is what the
DMA moves — the bytes-per-pull reduction is physical, not notational.

Adaptive early exit (DESIGN.md §12): with ``cert`` the kernel keeps a
per-query ``active`` lane in SMEM.  After every round-end step it evaluates
the certification predicate over the post-elimination survivors' rows —
each row's radius is ``a_l sqrt(max(Vhat, 0)) + b_l`` on the block-mean
scale, with ``Vhat`` from a second running-M2 accumulator when the
schedule's bound family is 'bernstein' — and if the top-``k_cert`` rows'
lower bounds clear every other survivor's upper bound, the query's
remaining group steps (tile DMAs + accumulate + prefetch) become masked
no-ops.  Eliminations keep running on the frozen accumulator (every
survivor froze at the same pull count, so scheduled-denominator means are a
positive rescale of the true means and every later ranking is unchanged),
the final extraction normalizes by the *actual* pull count, and a third
output reports per-query ``rounds_used``.

Coordinate-sampling pull mode (DESIGN.md §14): the kernel is generic in
the feature-tile width ``C`` — a group step DMAs surviving ``(R, C)``
feature tiles ``V_ref.at[tile, col]`` whether the plan calls them
'row'-mode blocks (C = min(512, d)) or 'coord'-mode narrow feature tiles
(C = coord_block, default 128 = the TPU lane width).  ``pull_mode='coord'``
needs ZERO kernel changes: `make_plan` re-blocks the feature axis so the
flat schedule's column ids index ``n_blocks = ceil(d / coord_block)``
narrow tiles.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import f32_tile_dot, pq_tile_dot, unpack_int4
from repro.core.schedule import END_BIT, PULL_BIT, SLOT_MASK, group_steps

__all__ = ["fused_cascade_pallas", "fused_cascade_batched_pallas",
           "pull_group_size"]

#: v5e: 128 MiB of VMEM and 1 MiB of SMEM per TensorCore; the kernel asks
#: for what its shapes need and refuses shapes that cannot fit.
_VMEM_CAP = 100 * 2**20
_SMEM_CAP_WORDS = 240_000
_ROW_CHUNK = 512        # rows per step of the chunked VMEM reductions
_DENSE_DMA_BYTES = 2**20   # VMEM bytes of one dense-phase slab DMA
_RING_BYTES = 2**18        # VMEM bytes of one half of the pull ring
_STAGE_ROWS = 2048         # staged rows a query reads back per DMA
_BIG = 2**30            # "no index" sentinel of the arg-reductions


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _take_max(buf, n_live, ch, R):
    """(max, flat arg) over the first ``n_live`` rows of a (rows, R) buffer.

    Flat order is ``row * R + lane``; NaN entries are skipped (they mark
    rows already extracted) and ties go to the lowest flat index — exactly
    `lax.top_k`'s semantics, one extraction at a time.  ``arg`` is ``_BIG``
    when no live entry is left.
    """
    rid = jax.lax.broadcasted_iota(jnp.int32, (ch, R), 0)
    lid = jax.lax.broadcasted_iota(jnp.int32, (ch, R), 1)

    def body(c, carry):
        m, arg = carry
        row0 = pl.multiple_of(c * ch, ch)
        x = buf[pl.ds(row0, ch), :]
        live = (row0 + rid) < n_live
        mc = jnp.max(jnp.where(jnp.logical_and(live, jnp.logical_not(
            jnp.isnan(x))), x, -jnp.inf))
        ac = jnp.min(jnp.where(jnp.logical_and(live, x == mc),
                               (row0 + rid) * R + lid, _BIG))
        better = jnp.logical_or(mc > m, jnp.logical_and(mc == m, ac < arg))
        return jnp.where(better, mc, m), jnp.where(better, ac, arg)

    return jax.lax.fori_loop(0, (n_live + ch - 1) // ch, body,
                             (jnp.float32(-jnp.inf), jnp.int32(_BIG)))


def _max_live(buf, n_live, ch, R):
    """Max over the first ``n_live`` rows of a (rows, R) buffer."""
    rid = jax.lax.broadcasted_iota(jnp.int32, (ch, R), 0)

    def body(c, m):
        row0 = pl.multiple_of(c * ch, ch)
        x = buf[pl.ds(row0, ch), :]
        return jnp.maximum(m, jnp.max(jnp.where((row0 + rid) < n_live, x,
                                                -jnp.inf)))

    return jax.lax.fori_loop(0, (n_live + ch - 1) // ch, body,
                             jnp.float32(-jnp.inf))


def _set_elem(buf, flat, value, R):
    """buf[flat // R, flat % R] = value, as one masked row store."""
    row = flat // R
    lid = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
    cur = buf[pl.ds(row, 1), :]
    buf[pl.ds(row, 1), :] = jnp.where(lid == flat - row * R,
                                      jnp.float32(value), cur)


def _get_elem(buf, flat, R):
    """buf[flat // R, flat % R] as a scalar (one row load + reduction)."""
    row = flat // R
    lid = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
    return jnp.max(jnp.where(lid == flat - row * R, buf[pl.ds(row, 1), :],
                             -jnp.inf))


def _tile_dot_lanes(tiles, qcol):
    """`f32_tile_dot` of T tiles, lane-dense: (T, R, C) x (1, C) -> (1, T R).

    The same products and the same tree of halves, so the same bits: the
    halves down to 128 lanes run across vregs as in `tree_sum`, and the
    rest across the rows of the transposed (T R, 128) block instead of
    within lanes, which costs the VPU far fewer shuffles.  ``C > 128``:
    the products' own halving stays ahead of the transpose, where a
    compiler that fuses a multiply into the add after it does so on both
    paths alike.
    """
    T, R, C = tiles.shape
    assert C > 128, C
    x = tiles.astype(jnp.float32) * qcol.astype(jnp.float32)[None]
    P = 1 << max(C - 1, 0).bit_length()
    if P != C:     # `tree_sum` pads to a power of two with zeros
        x = jnp.concatenate([x, jnp.zeros((T, R, P - C), x.dtype)], axis=-1)
    while x.shape[-1] > 128:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    x = x.reshape(T * R, x.shape[-1]).T
    while x.shape[0] > 1:
        h = x.shape[0] // 2
        x = x[:h] + x[h:]
    return x


def _sort_desc(key, val, key2, val2, T):
    """Stable descending sort of SMEM ``(key, val)[0:T]`` in place.

    Bottom-up merge sort ping-ponging through ``(key2, val2)``; the left
    run wins ties, so equal keys keep their slot order — the order
    `lax.top_k` returns.  Every pass pair ends back in ``(key, val)``.
    """
    def merge_pass(width, ks, vs, kd, vd):
        def run(r, _):
            lo = r * 2 * width
            mid = jnp.minimum(lo + width, T)
            hi = jnp.minimum(lo + 2 * width, T)

            def step(k, ij):
                i, j = ij
                ii = jnp.minimum(i, mid - 1)
                jj = jnp.minimum(j, hi - 1)
                a, b = ks[ii], ks[jj]
                left = jnp.logical_and(
                    i < mid, jnp.logical_or(j >= hi, a >= b))
                kd[k] = jnp.where(left, a, b)
                vd[k] = vs[jnp.where(left, ii, jj)]
                li = left.astype(jnp.int32)
                return i + li, j + 1 - li

            jax.lax.fori_loop(lo, hi, step, (lo, mid))
            return 0

        jax.lax.fori_loop(0, (T + 2 * width - 1) // (2 * width), run, 0)

    def two_passes(width):
        merge_pass(width, key, val, key2, val2)
        merge_pass(2 * width, key2, val2, key, val)
        return 4 * width

    jax.lax.while_loop(lambda w: w < T, two_passes, jnp.int32(1))


def _dense_chunk(R):
    """Tiles whose rows fill the 128 lanes of a dense pass's sums."""
    return max(1, 128 // R)


def _make_kernel(*, R, C, K, n_tiles, t_final, n_final, S, B, n_blocks,
                 qkind="none", adaptive=False, track_var=False, k_cert=1,
                 n_rounds=0, shared_cols=True, ch=8, ch_f=8, q_dtype=None,
                 t_dense, T_blk, xch, G):
    """Build the kernel body for the flat grid of ``G1 + B * S`` steps.

    ``qkind`` selects the pull arithmetic (DESIGN.md §10): 'none' (f32
    tile-dot), 'int8'/'int4' (the tensor-operand list grows by (vscale,
    qscale) and every pull dequantizes its exact int32 tile-dot — int4
    first sign-extends the nibble-packed tile with `unpack_int4`), or
    'pq' (the operand list grows by the f32 ``codebook`` instead and the
    pull is the `pq_tile_dot` LUT walk; queries stay f32).  ``C`` is the
    TRUE block width (denominators) — the DMA'd tile's last dim is
    whatever the stored operand carries (C, C/2 or S).
    With ``adaptive`` the scalar-prefetch list grows by the per-round
    ``cert`` coefficients, the outputs by ``rounds_used``, and the scratch
    by the certification row buffers (``track_var`` additionally carries
    the M2 accumulator for the variance-aware 'bernstein' radii);
    ``k_cert`` is the *contract* top-K the predicate certifies (K above
    is the extraction width ``k_out``).  ``ch``/``ch_f`` are the row
    chunks of the (n_tiles, R) and (n_final, R) buffer reductions;
    ``q_dtype`` is the true dtype of a query block passed 32-bit wide.
    ``t_dense`` round-1 columns run in the dense phase, ``T_blk`` tiles
    a step, and each query reads its staged sums back ``xch`` rows a DMA;
    ``S`` counts each query's own grid steps, each pulling up to ``G``
    same-column survivor tiles (`repro.core.schedule.group_steps`).
    """
    dims = (((1,), (1,)), ((), ()))        # (1, C) x (R, C) -> (1, R)
    n_tblk = -(-n_tiles // T_blk) if t_dense else 0
    T_last = n_tiles - (n_tblk - 1) * T_blk    # tiles of the last group
    Bq = B if shared_cols else 1     # queries one dense step scores
    per_group = n_tblk * t_dense     # dense steps per query group
    G1 = (1 if shared_cols else B) * per_group
    W = t_dense + S                  # columns per query in ``cols``
    x_rows = _round_up(n_tiles, 8)   # staged rows read back per query
    chunk = _dense_chunk(R)          # tiles one fp32 dense pass sums

    def kernel(code_ref, rmeta_ref, *more):
        if adaptive:
            cert_ref, cols_ref, nv_ref, V_ref, q_ref, *rest = more
        else:
            cols_ref, nv_ref, V_ref, q_ref, *rest = more
            cert_ref = None
        vs_ref = qs_ref = cb_ref = None
        if qkind in ("int8", "int4"):
            vs_ref, qs_ref, *rest = rest
        elif qkind == "pq":
            cb_ref, *rest = rest
        ids_ref, vals_ref, *rest = rest
        rused_ref = stage = stage2 = None
        if adaptive:
            rused_ref, *rest = rest
        if G1:
            stage, *rest = rest
            if track_var:
                stage2, *rest = rest
        acc, *rest = rest
        acc2 = None
        if track_var:
            acc2, *rest = rest
        vbuf, surv, key2, val2, keys, cand, st, *rest = rest
        if adaptive:
            minlb, bufM, bufU, bufL, *rest = rest
        if G1:
            dbuf, dacc, *rest = rest
            dacc2 = None
            if track_var:
                dacc2, *rest = rest
            xbuf, sem, dsem = rest
        else:
            (sem,) = rest
        g = pl.program_id(0)
        in_q = g >= G1                       # a query's own step
        gq = jnp.maximum(g - G1, 0)
        b, i = jax.lax.div(gq, S), jax.lax.rem(gq, S)
        nv = nv_ref[0]
        code = code_ref[i]
        slot = code & SLOT_MASK
        pull = jnp.logical_and(in_q, (code & PULL_BIT) != 0)
        end = jnp.logical_and(in_q, (code & END_BIT) != 0)

        def col_at(bb, ii):      # column of query bb's own step ii
            return (cols_ref[t_dense + ii] if shared_cols
                    else cols_ref[bb * W + t_dense + ii])

        col = col_at(b, i)
        dslot = jax.lax.rem(i, 2)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)

        def q_block(qi, cl):
            """(1, Cq) block ``cl`` of query ``qi``, at its true dtype."""
            qcol = q_ref[qi, pl.ds(cl, 1), :]
            if q_dtype is not None:      # narrow values held 32-bit wide
                qcol = qcol.astype(q_dtype)
            return qcol

        def tile_part(tilebuf, qcol, cl, tile, qi):
            """(1, R) partial products of one pulled (R, Cs) tile."""
            if qkind == "pq":
                # per-pull LUT of query-vs-codeword products + one-hot
                # lookups per row — the SHARED `pq_tile_dot`, so the jnp
                # fallbacks run literally the same f32 ops (bit-exact)
                cb = cb_ref[pl.ds(cl, 1)][0]       # (S, n_codes, w)
                return pq_tile_dot(tilebuf, qcol[0], cb)[None]
            if qkind in ("int8", "int4"):
                # int8 x int8 -> int32 on the MXU, then dequantize with the
                # tile/block scale product.  The jnp fallback does the
                # identical (exact) integer dot and the identical two float
                # ops per entry, so the paths stay bit-exact.
                if qkind == "int4":
                    tilebuf = unpack_int4(tilebuf)
                raw = jax.lax.dot_general(qcol, tilebuf, dims,
                                          preferred_element_type=jnp.int32)
                gi = tile // 128
                lane128 = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
                vs = jnp.max(jnp.where(lane128 == tile - gi * 128,
                                       vs_ref[cl, pl.ds(gi, 1), :],
                                       -jnp.inf), axis=1, keepdims=True)
                return raw.astype(jnp.float32) * (
                    vs * qs_ref[qi * n_blocks + cl])
            # f32 products + lane reduction — the SHARED `f32_tile_dot`,
            # so the jnp fallbacks sum in the same order (bit-exact)
            return f32_tile_dot(tilebuf, qcol)                  # (1, R)

        def masked_means(tile, denom):
            """(1, R) means of ``tile``'s rows; invalid rows -> -inf."""
            mu = acc[pl.ds(tile, 1), :] / denom
            valid = tile * R + lane < nv
            return jnp.where(valid, mu, -jnp.inf), mu, valid

        if G1:
            # -- the dense phase: round 1 for the whole batch, T_blk tiles
            # of one column a step, each tile group's sums staged to HBM
            def dense_at(gg):    # step -> (query group, tile group, column)
                qg = jax.lax.div(gg, per_group)
                rem = gg - qg * per_group
                tb = jax.lax.div(rem, t_dense)
                c = rem - tb * t_dense
                cl = cols_ref[c] if shared_cols else cols_ref[qg * W + c]
                return qg, tb, c, cl

            def dense_dma(tb, cl, ds, wait):
                def run(t0, n):
                    dst = dbuf.at[ds] if n == T_blk else dbuf.at[
                        ds, pl.ds(0, n)]
                    cp = pltpu.make_async_copy(V_ref.at[pl.ds(t0, n), cl],
                                               dst, dsem.at[ds])
                    cp.wait() if wait else cp.start()
                if T_last == T_blk:
                    run(pl.multiple_of(tb * T_blk, 8), T_blk)
                    return
                if n_tblk > 1:    # the last group is short: its own size
                    pl.when(tb < n_tblk - 1)(
                        lambda: run(pl.multiple_of(tb * T_blk, 8), T_blk))
                pl.when(tb == n_tblk - 1)(
                    lambda: run((n_tblk - 1) * T_blk, T_last))

            @pl.when(g == 0)
            def _start_dense():
                _, tb0, _, cl0 = dense_at(0)
                dense_dma(tb0, cl0, 0, wait=False)

            @pl.when(g < G1)
            def _dense():
                qg, tb, c, cl = dense_at(g)
                ds = jax.lax.rem(g, 2)
                dense_dma(tb, cl, ds, wait=True)

                @pl.when(g + 1 < G1)
                def _():
                    _, ntb, _, ncl = dense_at(g + 1)
                    dense_dma(ntb, ncl, 1 - ds, wait=False)

                @pl.when(c == 0)
                def _():   # sums start at 0.0, as the fallbacks' do
                    dacc[...] = jnp.zeros(dacc.shape, jnp.float32)
                    if track_var:
                        dacc2[...] = jnp.zeros(dacc2.shape, jnp.float32)

                n_t = jnp.where(tb == n_tblk - 1, T_last, T_blk)

                lanes = pl.ds(0, R)    # the stage is 128 lanes wide

                def add(j, rows, part):
                    dacc[j, rows, lanes] = dacc[j, rows, lanes] + part
                    if track_var:
                        dacc2[j, rows, lanes] = (dacc2[j, rows, lanes]
                                                 + part * part)

                def one_query(j, _):
                    qi = j if shared_cols else qg
                    qcol = q_block(qi, cl)
                    if qkind in ("int8", "int4"):
                        def per_tile(t, _):  # the pull step's own arithmetic
                            add(j, pl.ds(t, 1),
                                tile_part(dbuf[ds, t], qcol, cl,
                                          tb * T_blk + t, qi))
                            return 0
                        jax.lax.fori_loop(0, n_t, per_tile, 0)
                    else:
                        def per_chunk(k, _):  # `chunk` tiles a pass
                            t0 = pl.multiple_of(k * chunk, chunk)
                            slab = dbuf[ds, pl.ds(t0, chunk)]
                            if qkind == "pq":
                                cb = cb_ref[pl.ds(cl, 1)][0]
                                add(j, pl.ds(t0, chunk),
                                    pq_tile_dot(slab, qcol[0], cb))
                            elif C > 128:
                                sums = _tile_dot_lanes(slab, qcol)
                                for t in range(chunk):   # (1, R) a tile
                                    add(j, pl.ds(t0 + t, 1),
                                        sums[:, t * R:(t + 1) * R])
                            else:
                                add(j, pl.ds(t0, chunk),
                                    f32_tile_dot(slab, qcol))
                            return 0
                        jax.lax.fori_loop(0, (n_t + chunk - 1) // chunk,
                                          per_chunk, 0)
                    return 0
                jax.lax.fori_loop(0, Bq, one_query, 0)

                @pl.when(c == t_dense - 1)
                def _stage():      # the group's round-1 sums, to HBM
                    q0 = 0 if shared_cols else qg
                    rows = pl.ds(pl.multiple_of(tb * T_blk, 8), T_blk)
                    for src, dst in ((dacc, stage), (dacc2, stage2)):
                        if src is not None:
                            cp = pltpu.make_async_copy(
                                src, dst.at[pl.ds(q0, Bq), rows], dsem.at[2])
                            cp.start()
                            cp.wait()

        @pl.when(jnp.logical_and(in_q, i == 0))
        def _init():  # per-query state (re-entered at each b in the batch)
            if G1:   # round 1's sums, staged by the dense phase
                def fetch(k, _):
                    # the last chunk may overlap the one before: it copies
                    # the same values again
                    r0 = pl.multiple_of(jnp.minimum(k * xch, x_rows - xch),
                                        8)
                    for src, dst in ((stage, acc), (stage2, acc2)):
                        if src is not None:
                            cp = pltpu.make_async_copy(
                                src.at[b, pl.ds(r0, xch)], xbuf, dsem.at[2])
                            cp.start()
                            cp.wait()

                            def put(r, _):
                                rr = pl.multiple_of(r * 8, 8)
                                dst[pl.ds(r0 + rr, 8), :] = xbuf[
                                    pl.ds(rr, 8), pl.ds(0, R)]
                                return 0
                            jax.lax.fori_loop(0, xch // 8, put, 0)
                    return 0
                jax.lax.fori_loop(0, -(-x_rows // xch), fetch, 0)
            else:
                def zero(c, _):
                    row0 = pl.multiple_of(c * ch, ch)
                    acc[pl.ds(row0, ch), :] = jnp.zeros((ch, R), jnp.float32)
                    if track_var:
                        acc2[pl.ds(row0, ch), :] = jnp.zeros((ch, R),
                                                             jnp.float32)
                    return 0
                jax.lax.fori_loop(0, acc.shape[0] // ch, zero, 0)

            def ident(j, _):
                surv[j] = j
                return 0
            jax.lax.fori_loop(0, n_tiles, ident, 0)
            st[0] = 0                      # round cursor
            if adaptive:
                st[1] = 1                  # active lane
                st[2] = t_final            # t_stop (actual pulls)
                rused_ref[b] = n_rounds

        def group_len(s0, r):
            """Tiles of the group opening at slot ``s0`` in the round at
            cursor ``r``: the final-coverage steps follow the last round."""
            n_surv = rmeta_ref[3 * r + 1]
            if n_rounds:
                n_surv = jnp.where(r < n_rounds, n_surv, n_final)
            else:
                n_surv = n_final
            return jnp.minimum(G, n_surv - s0)

        def ring_copy(tile, cl, h, t):   # one tile into slot t of half h
            return pltpu.make_async_copy(V_ref.at[tile, cl], vbuf.at[h, t],
                                         sem.at[h])

        def start_group(bb, ii, h, r, active=True, ident=False):
            """Start every tile DMA of query ``bb``'s own step ``ii`` into
            ring half ``h``; ``ident``: a fresh query's identity slots."""
            ncode = code_ref[ii]

            @pl.when(jnp.logical_and(active, (ncode & PULL_BIT) != 0))
            def _():
                s0 = ncode & SLOT_MASK
                cl = col_at(bb, ii)

                def one(t, _):
                    ring_copy(s0 + t if ident else surv[s0 + t], cl, h,
                              t).start()
                    return 0
                jax.lax.fori_loop(0, group_len(s0, r), one, 0)

        @pl.when(g == max(G1 - 1, 0))
        def _start_first():  # every later step is prefetched by the previous
            start_group(0, 0, 0, 0, ident=True)   # query 0's identity slots

        # a certified (inactive) query's remaining pulls are masked no-ops:
        # no DMA wait, no accumulate — and _warm below starts no DMA for it
        do_pull = jnp.logical_and(pull, st[1] == 1) if adaptive else pull

        @pl.when(do_pull)
        def _pull():   # the group's tiles, each added to its own row
            n = group_len(slot, st[0])

            def wait(t, _):
                ring_copy(0, col, dslot, t).wait()
                return 0
            jax.lax.fori_loop(0, n, wait, 0)
            qcol = q_block(b, col)

            def add(t, part):
                rows = pl.ds(surv[slot + t], 1)
                acc[rows, :] = acc[rows, :] + part
                if track_var:
                    acc2[rows, :] = acc2[rows, :] + part * part

            if qkind == "none":
                def per_chunk(k, _):   # `chunk` tiles a pass, lane-dense
                    t0 = pl.multiple_of(k * chunk, chunk)
                    slab = vbuf[dslot, pl.ds(t0, chunk)]
                    if C > 128:
                        sums = _tile_dot_lanes(slab, qcol)
                        parts = [sums[:, t * R:(t + 1) * R]
                                 for t in range(chunk)]
                    else:
                        sums = f32_tile_dot(slab, qcol)     # (chunk, R)
                        parts = [sums[t:t + 1] for t in range(chunk)]
                    for t, part in enumerate(parts):
                        pl.when(t0 + t < n)(
                            functools.partial(add, t0 + t, part))
                    return 0
                jax.lax.fori_loop(0, (n + chunk - 1) // chunk, per_chunk, 0)
            else:
                def per_tile(t, _):    # the quantized tiers: a tile a pass
                    add(t, tile_part(vbuf[dslot, t], qcol, col,
                                     surv[slot + t], b))
                    return 0
                jax.lax.fori_loop(0, n, per_tile, 0)

        @pl.when(end)
        def _eliminate():
            r = st[0]
            t_cum = rmeta_ref[3 * r]
            T, keep = rmeta_ref[3 * r + 1], rmeta_ref[3 * r + 2]
            denom = (t_cum * C).astype(jnp.float32)

            def score(s, _):  # slot-ordered masked tile-max means
                keys[s] = jnp.max(masked_means(surv[s], denom)[0])
                return 0
            jax.lax.fori_loop(0, T, score, 0)
            _sort_desc(keys, surv, key2, val2, T)   # top `keep` survive

            if adaptive:
                # certification over the post-elimination survivors' rows
                # (DESIGN.md §12): radius_i = a sqrt(max(Vhat_i, 0)) + b,
                # fire when the top-k_cert rows' lower bounds clear every
                # other valid row's upper bound
                @pl.when(st[1] == 1)
                def _certify():
                    a = cert_ref[2 * r]
                    bconst = cert_ref[2 * r + 1]
                    denomC = denom * jnp.float32(C)

                    def fill(s, _):
                        tile = surv[s]
                        m, mu, valid = masked_means(tile, denom)
                        if track_var:
                            v = (acc2[pl.ds(tile, 1), :] / denomC - mu * mu)
                            rad = a * jnp.sqrt(jnp.maximum(v, 0.0)) + bconst
                        else:
                            rad = jnp.full_like(mu, bconst)
                        bufM[pl.ds(s, 1), :] = m
                        bufU[pl.ds(s, 1), :] = jnp.where(valid, mu + rad,
                                                         -jnp.inf)
                        bufL[pl.ds(s, 1), :] = jnp.where(valid, mu - rad,
                                                         -jnp.inf)
                        return 0
                    jax.lax.fori_loop(0, keep, fill, 0)
                    minlb[0] = jnp.float32(jnp.inf)

                    def take(j, _):  # top-k_cert rows by mean, as extract
                        _, arg = _take_max(bufM, keep, ch, R)
                        have = arg < _BIG
                        argc = jnp.where(have, arg, 0)
                        lb = jnp.where(have, _get_elem(bufL, argc, R),
                                       -jnp.inf)
                        minlb[0] = jnp.minimum(minlb[0], lb)

                        @pl.when(have)
                        def _():
                            _set_elem(bufU, argc, -jnp.inf, R)
                            _set_elem(bufM, argc, jnp.nan, R)  # distinct rows
                        return 0
                    jax.lax.fori_loop(0, k_cert, take, 0)

                    @pl.when(minlb[0] >= _max_live(bufU, keep, ch, R))
                    def _fire():
                        st[1] = 0
                        st[2] = t_cum
                        rused_ref[b] = r + 1

            st[0] = r + 1

        # prefetch the next step's group (post-elimination survivor ids);
        # frozen queries prefetch nothing
        @pl.when(jnp.logical_and(in_q, i < S - 1))
        def _warm():
            start_group(b, i + 1, 1 - dslot, st[0],
                        active=(st[1] == 1) if adaptive else True)

        @pl.when(jnp.logical_and(jnp.logical_and(in_q, i == S - 1),
                                 b < B - 1))
        def _warm_next_query():  # next query restarts on identity slots
            start_group(b + 1, 0, 0, 0, ident=True)

        @pl.when(jnp.logical_and(in_q, i == S - 1))
        def _finalize():
            if adaptive:   # normalize by the query's ACTUAL pull count
                denom_f = (jnp.maximum(st[2], 1) * C).astype(jnp.float32)
            else:
                denom_f = jnp.float32(max(1, t_final) * C)

            def fill(s, _):
                cand[pl.ds(s, 1), :] = masked_means(surv[s], denom_f)[0]
                return 0
            jax.lax.fori_loop(0, n_final, fill, 0)

            def extract(j, _):  # descending, lowest-index tie-break
                # extracted entries become NaN: they can never tie with the
                # running max again (an -inf marker would re-extract the
                # same entry once the max itself reaches -inf, duplicating
                # candidates whenever fewer than K rows are valid —
                # exactly `lax.top_k`'s distinct-index semantics)
                m, arg = _take_max(cand, n_final, ch_f, R)
                s_idx = arg // R
                ids_ref[b * K + j] = surv[s_idx] * R + (arg - s_idx * R)
                vals_ref[b * K + j] = m
                _set_elem(cand, arg, jnp.nan, R)
                return 0
            jax.lax.fori_loop(0, K, extract, 0)

    return kernel


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of a VMEM buffer under the (8 * 4 / itemsize, 128) tiling."""
    item = jnp.dtype(dtype).itemsize
    *lead, s, l = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    n = 1
    for x in lead:
        n *= x
    return n * _round_up(s, 8 * 4 // item) * _round_up(l, 128) * item


def _resolve_qkind(Cs, vscale, qscale, codebook, packed_int4):
    """Classify the tier from the wrapper operands; returns (qkind, C).

    ``Cs`` is the stored operand's last dim; ``C`` the true block width
    the kernel's denominators use — 2*Cs for nibble-packed int4,
    S*subdims from the codebook shape for pq, Cs otherwise.
    """
    if codebook is not None:
        if vscale is not None or qscale is not None or packed_int4:
            raise ValueError("codebook (pq) excludes vscale/qscale/"
                             "packed_int4")
        return "pq", codebook.shape[1] * codebook.shape[3]
    if (vscale is not None) != (qscale is not None):
        raise ValueError("vscale and qscale must be passed together")
    if packed_int4:
        if vscale is None:
            raise ValueError("packed_int4 needs vscale/qscale (W4A8)")
        return "int4", 2 * Cs
    return ("int8" if vscale is not None else "none"), Cs


def pull_group_size(R: int, Cs: int, dtype) -> int:
    """G: the most survivor tiles one grid step of rounds 2+ pulls.

    Taken from the stored tile as the dense phase's ``T_blk`` is: whole
    lane-dense passes of `_dense_chunk` tiles, about ``_RING_BYTES`` of
    VMEM a ring half (16 tiles of (8, 512) at fp32, bf16 or int8).
    """
    unit = _dense_chunk(R)
    tile = _vmem_bytes((R, Cs), dtype)
    return unit * max(1, _RING_BYTES // (unit * tile))


def _fused(V4, Qb, slotcode, rounds_meta, cols, *, t_dense, **kw):
    """Shared body of the single-query and batched entry points.

    Groups the per-step list on the host (`group_steps`), keeps the
    columns of each group's first step, and launches the kernel.
    """
    if isinstance(slotcode, jax.core.Tracer):
        raise ValueError(
            "slotcode must be host data (numpy): the kernel's grid is "
            "derived from it")
    code = np.asarray(slotcode)
    B = Qb.shape[0]
    S = code.shape[0]
    cols = jnp.asarray(cols, jnp.int32)
    W = t_dense + S                  # dense columns, then one per step
    if cols.shape not in ((W,), (B, W)):
        raise ValueError(
            f"cols must be (t_dense + S,) or (B, t_dense + S) with t_dense "
            f"= {t_dense}, S = {S} steps and B = {B}; got {cols.shape} "
            f"(`FlatSchedule.col_pos` gives the positions)")
    _, _, R, Cs = V4.shape
    G = pull_group_size(R, Cs, V4.dtype)
    code_g, first = group_steps(code, G)
    keep = np.concatenate([np.arange(t_dense), t_dense + first])
    return _launch(V4, Qb, jnp.asarray(code_g), rounds_meta,
                   jnp.take(cols, jnp.asarray(keep, jnp.int32), axis=-1),
                   t_dense=t_dense, G=G, **kw)


@functools.partial(jax.jit, static_argnames=(
    "n_arms", "K", "t_final", "n_final", "t_dense", "k_out", "k_cert",
    "track_var", "packed_int4", "interpret", "G"))
def _launch(V4, Qb, slotcode, rounds_meta, cols, *, n_arms, K, t_final,
            n_final, t_dense, k_out, n_valid, vscale, qscale, codebook,
            packed_int4, cert, k_cert, track_var, interpret, G):
    """One ``pallas_call`` over the grouped steps (``slotcode`` (S_g,),
    ``cols`` (t_dense + S_g,) or (B, t_dense + S_g)), ``G`` tiles a step."""
    n_tiles, n_blocks, R, Cs = V4.shape
    qkind, C = _resolve_qkind(Cs, vscale, qscale, codebook, packed_int4)
    if not interpret and qkind in ("int4", "pq"):
        raise NotImplementedError(
            f"the {qkind} tier of the fused cascade has no Mosaic lowering "
            f"yet; serve int4/pq with interpret=True (CPU) or use the "
            f"fp32/int8 tiers on TPU")
    adaptive = cert is not None
    K = K if k_out is None else k_out   # K sizes the extraction/output
    if n_valid is None:
        n_valid = n_arms
    B = Qb.shape[0]
    S = slotcode.shape[0]
    shared_cols = cols.ndim == 1
    W = t_dense + S
    n_rounds = rounds_meta.shape[0] - 1
    ch = min(_ROW_CHUNK, _round_up(n_tiles, 8))
    n_rows = _round_up(n_tiles, ch)
    ch_f = _round_up(n_final, 8)
    q_dtype = None
    if Qb.dtype.itemsize < 4:
        # a dynamic one-row load of packed (int8 / bf16) sublanes is
        # refused; the block is tiny, so widen it to 32 bits and narrow
        # the pulled row back in-kernel (an exact round trip)
        q_dtype = Qb.dtype
        Qb = Qb.astype(jnp.int32 if jnp.issubdtype(q_dtype, jnp.integer)
                       else jnp.float32)
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),                  # V4: manual DMA
        pl.BlockSpec(memory_space=pltpu.VMEM),              # all queries
    ]
    operands = [V4, Qb]
    if qkind in ("int8", "int4"):
        # vscale transposed and lane-dense: (n_blocks, n_tiles/128, 128)
        g = -(-n_tiles // 128)
        vsT = jnp.asarray(vscale, jnp.float32).T
        vsT = jnp.pad(vsT, ((0, 0), (0, g * 128 - n_tiles)))
        in_specs += [pl.BlockSpec(memory_space=pltpu.VMEM),
                     pl.BlockSpec(memory_space=pltpu.SMEM)]
        operands += [vsT.reshape(n_blocks, g, 128),
                     jnp.asarray(qscale, jnp.float32).reshape(-1)]
    elif qkind == "pq":
        in_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))  # codebook
        operands.append(jnp.asarray(codebook, jnp.float32))
    scalars = [slotcode.astype(jnp.int32),
               rounds_meta.astype(jnp.int32).reshape(-1)]
    if adaptive:
        scalars.append(jnp.asarray(cert, jnp.float32).reshape(-1))
    scalars += [cols.reshape(-1), jnp.asarray(n_valid, jnp.int32).reshape(1)]
    n_sums = 2 if track_var else 1
    Bq = B if shared_cols else 1
    # HBM refuses a slice of a minor dim narrower than 128 lanes, so the
    # staged sums sit in the first R of L lanes
    L = _round_up(R, 128)

    def layout(T_blk, xch):
        """(out_shape, scratch, dense VMEM bytes) with ``T_blk`` tiles per
        dense step and ``xch`` staged rows per read-back DMA."""
        out_shape = [jax.ShapeDtypeStruct((B * K,), jnp.int32),
                     jax.ShapeDtypeStruct((B * K,), jnp.float32)]
        if adaptive:
            out_shape.append(jax.ShapeDtypeStruct((B,), jnp.int32))
        if t_dense:   # round-1 sums staged in HBM; dropped by the wrapper
            rows = _round_up(n_tiles, T_blk)
            out_shape += [jax.ShapeDtypeStruct((B, rows, L), jnp.float32)
                          ] * n_sums
        # accumulator, plus the running M2 feeding 'bernstein' radii
        scratch = [pltpu.VMEM((n_rows, R), jnp.float32)] * n_sums
        scratch += [
            pltpu.VMEM((2, G, R, Cs), V4.dtype),  # the pull ring: 2 groups
            pltpu.SMEM((n_tiles,), jnp.int32),    # survivor tile ids
            pltpu.SMEM((n_tiles,), jnp.float32),  # merge-sort ping-pong keys
            pltpu.SMEM((n_tiles,), jnp.int32),    # merge-sort ping-pong ids
            pltpu.SMEM((n_tiles,), jnp.float32),  # elimination sort keys
            pltpu.VMEM((ch_f, R), jnp.float32),   # final extraction rows
            pltpu.SMEM((3,), jnp.int32),          # round cursor, active, t_stop
        ]
        if adaptive:
            scratch += [pltpu.SMEM((1,), jnp.float32)]        # min lower bound
            scratch += [pltpu.VMEM((n_rows, R), jnp.float32)] * 3  # M / U / L
        dense = []
        if t_dense:
            dense.append(pltpu.VMEM((2, T_blk, R, Cs), V4.dtype))  # slabs
            dense += [pltpu.VMEM((Bq, T_blk, L), jnp.float32)] * n_sums
            dense.append(pltpu.VMEM((xch, L), jnp.float32))  # read-back
        scratch += dense
        scratch.append(pltpu.SemaphoreType.DMA((2,)))
        if t_dense:   # the two slab slots + the staging transfers
            scratch.append(pltpu.SemaphoreType.DMA((3,)))
        return out_shape, scratch, sum(_vmem_bytes(d.shape, d.dtype)
                                       for d in dense)

    def vmem_of(scratch):
        vmem = sum(_vmem_bytes(s.shape, s.dtype) for s in scratch
                   if getattr(s, "memory_space", None) == pltpu.VMEM)
        vmem += _vmem_bytes(Qb.shape, Qb.dtype)
        return vmem + sum(_vmem_bytes(x.shape, x.dtype)
                          for x in operands[2:] if x.ndim > 1)

    # dense steps move about _DENSE_DMA_BYTES each, whole passes of 8-row
    # aligned tiles; when VMEM is short the slabs and the read-back shrink
    unit = math.lcm(8, _dense_chunk(R))
    tile_bytes = _vmem_bytes((R, Cs), V4.dtype)
    T_blk = min(max(unit, _DENSE_DMA_BYTES // tile_bytes // unit * unit),
                _round_up(n_tiles, unit))
    xch = min(_round_up(n_tiles, 8), _STAGE_ROWS)
    out_shape, scratch, dense_vmem = layout(T_blk, xch)
    while (not interpret and vmem_of(scratch) > _VMEM_CAP
           and max(T_blk // unit, xch // 8) > 1):
        T_blk = max(unit, T_blk // 2 // unit * unit)
        xch = max(8, xch // 2 // 8 * 8)
        out_shape, scratch, dense_vmem = layout(T_blk, xch)
    G1 = (1 if shared_cols else B) * -(-n_tiles // T_blk) * t_dense
    params = None
    if not interpret:
        # the dense phase's buffers, the whole query block beyond a
        # double-buffered query and the dense columns ride above the caps:
        # a shape is refused by the same rule as without a dense phase
        extra = dense_vmem + max(0, _vmem_bytes(Qb.shape, Qb.dtype)
                                 - 2 * _vmem_bytes(Qb.shape[1:], Qb.dtype))
        vmem = vmem_of(scratch)
        smem = sum(x.size for x in scalars) + 4 * n_tiles + B * (2 * K + 1)
        if qkind in ("int8", "int4"):
            smem += qscale.size
        if (vmem - extra > _VMEM_CAP
                or smem - cols.size // W * t_dense > _SMEM_CAP_WORDS):
            raise ValueError(
                f"fused cascade does not fit one TensorCore: VMEM "
                f"{vmem} B (cap {_VMEM_CAP}), SMEM {smem} words (cap "
                f"{_SMEM_CAP_WORDS}) for n_tiles={n_tiles}, S={S}, B={B}")
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(_VMEM_CAP + extra, vmem + 8 * 2**20))
    out_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] * (
        3 if adaptive else 2)
    out_specs += [pl.BlockSpec(memory_space=pl.ANY)] * (
        len(out_shape) - len(out_specs))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(G1 + B * S,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
    )
    kernel = _make_kernel(R=R, C=C, K=K, n_tiles=n_tiles, t_final=t_final,
                          n_final=n_final, S=S, B=B, n_blocks=n_blocks,
                          qkind=qkind, adaptive=adaptive,
                          track_var=track_var, k_cert=k_cert,
                          n_rounds=n_rounds, shared_cols=shared_cols,
                          ch=ch, ch_f=ch_f, q_dtype=q_dtype,
                          t_dense=t_dense, T_blk=T_blk, xch=xch, G=G)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(out_shape),
        compiler_params=params,
        interpret=interpret,
        name="fused_cascade",       # the kernel's name in device traces
    )(*scalars, *operands)
    ids, vals = out[0].reshape(B, K), out[1].reshape(B, K)
    return (ids, vals, out[2]) if adaptive else (ids, vals)


def fused_cascade_pallas(V4, qb, slotcode, rounds_meta, cols, *, n_arms: int,
                         K: int, t_final: int, n_final: int, t_dense: int,
                         k_out: int = None, n_valid=None,
                         vscale=None, qscale=None, codebook=None,
                         packed_int4: bool = False, cert=None,
                         k_cert: int = 1, track_var: bool = False,
                         interpret: bool = False):
    """Single-query fused cascade: ONE pallas_call for all rounds.

    V4:  (n_tiles, n_blocks, R, C) tile-major data (stays in HBM);
    float for the fp32 path, int8 for the quantized path, nibble-packed
    int8 (last dim C/2) with ``packed_int4=True``, uint8 codes (last dim
    S) with ``codebook``.
    qb:  (n_blocks, C) blocked query (VMEM-resident) — f32 on the fp32
    AND pq paths, int8 on the int8/int4 (W4A8) paths.
    slotcode/rounds_meta: see `FlatSchedule.packed`; ``slotcode`` is host
    data (numpy), which this entry point groups into the kernel's grid
    steps (`repro.core.schedule.group_steps`); cols (t_dense + S,): the
    dense phase's round-1 column-block ids, then one per step
    (``perm[flat.col_pos]``); ``t_dense`` is `FlatSchedule.t_dense`.
    k_out: number of final candidates extracted in-kernel (default K).
    Shard-local callers ask for k_out > K so the K winners come back with a
    threshold candidate for bound-gap computation; K only sizes the
    schedule while k_out sizes the output.  Must satisfy
    ``K <= k_out <= n_final * R``.
    n_valid: rows >= n_valid never win a ranking (default ``n_arms``);
    accepts a traced scalar, so shards can mask their own slice of a
    caller-padded table in-cascade (DESIGN.md §7).
    vscale/qscale: per-tile table scales (n_tiles, n_blocks) and per-block
    query scales (n_blocks,) for int8/int4 operands (`repro.core.quantize`,
    DESIGN.md §10); both or neither must be given.
    codebook: (n_blocks, S, n_codes, subdims) f32 pq codebook
    (`repro.core.quantize.pq_train`), fully VMEM-resident; excludes
    vscale/qscale/packed_int4.
    cert: (rounds+1, 2) f32 per-round certification coefficients
    (`repro.core.schedule.cert_coeffs`) — enables adaptive early exit
    (DESIGN.md §12); ``k_cert`` is the contract top-K the predicate
    certifies and ``track_var`` carries the running M2 accumulator the
    'bernstein' radii read.
    Returns (ids (k_out,) int32, vals (k_out,) f32) — vals are unscaled
    block means, identical to the unfused path before its padding rescale.
    With ``cert`` a third output ``rounds_used`` (int32 scalar) reports
    how many elimination rounds actually pulled before certification.
    """
    out = _fused(V4, qb[None], slotcode, rounds_meta, cols, n_arms=n_arms,
                 K=K, t_final=t_final, n_final=n_final, t_dense=t_dense,
                 k_out=k_out,
                 n_valid=n_valid, vscale=vscale,
                 qscale=None if qscale is None else qscale[None],
                 codebook=codebook, packed_int4=packed_int4, cert=cert,
                 k_cert=k_cert, track_var=track_var, interpret=interpret)
    return tuple(x[0] for x in out)


def fused_cascade_batched_pallas(V4, Qb, slotcode, rounds_meta, cols, *,
                                 n_arms: int, K: int, t_final: int,
                                 n_final: int, t_dense: int,
                                 k_out: int = None,
                                 n_valid=None, vscale=None, qscale=None,
                                 codebook=None, packed_int4: bool = False,
                                 cert=None, k_cert: int = 1,
                                 track_var: bool = False,
                                 interpret: bool = False):
    """Batched fused cascade: one flat grid serves the whole batch.

    Qb: (B, n_blocks, C) blocked queries; cols: (B, t_dense + S) per-query
    pull columns, or (t_dense + S,) when the whole batch shares one
    permutation (the decode path — it keeps SMEM at one word per step and
    lets each dense round-1 step score every query).  One dispatch serves
    the whole decode batch; per-query state is re-initialized at each
    query's first grid step.  ``k_out`` (default K) widens the
    in-kernel final extraction and ``n_valid`` (default ``n_arms``, may be
    traced) masks caller-padding rows exactly as in
    `fused_cascade_pallas`.  For int8 operands pass ``vscale`` (n_tiles,
    n_blocks) and per-query ``qscale`` (B, n_blocks) (DESIGN.md §10); for
    nibble-packed int4 tiles additionally set ``packed_int4=True``; for
    product-quantized tiles pass ``codebook`` instead (uint8 code table,
    f32 queries) — tiers resolve exactly as in `fused_cascade_pallas`.
    ``cert``/``k_cert``/``track_var`` enable per-query adaptive early exit
    exactly as in `fused_cascade_pallas` — each query carries its own
    ``active`` lane, so one certified query's no-op steps never disturb
    its batchmates.
    Returns (ids (B, k_out) int32, vals (B, k_out) f32), unscaled; with
    ``cert`` also ``rounds_used (B,) int32``.
    """
    return _fused(V4, Qb, slotcode, rounds_meta, cols, n_arms=n_arms, K=K,
                  t_final=t_final, n_final=n_final, t_dense=t_dense,
                  k_out=k_out,
                  n_valid=n_valid, vscale=vscale, qscale=qscale,
                  codebook=codebook, packed_int4=packed_int4, cert=cert,
                  k_cert=k_cert, track_var=track_var, interpret=interpret)
