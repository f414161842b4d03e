"""Pure oracles for every Pallas kernel (the allclose reference)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = ["gather_block_dot_ref", "blocked_matvec_ref", "fused_cascade_ref"]


def gather_block_dot_ref(V4: jnp.ndarray, idx: jnp.ndarray,
                         cols: jnp.ndarray, qsel: jnp.ndarray) -> jnp.ndarray:
    """Partial inner products for surviving arm tiles over selected blocks.

    V4:   (n_tiles, n_blocks, R, C) tile-major data
    idx:  (T,)  surviving tile ids
    cols: (dt,) coordinate-block ids to pull this round
    qsel: (dt, C) the query restricted to those blocks
    out:  (T, R) float32 partial sums  sum_b  V4[idx_t, cols_b] @ qsel_b
    """
    Vsel = V4[idx[:, None], cols[None, :]]        # (T, dt, R, C)
    return jnp.einsum("tbrc,bc->tr", Vsel, qsel,
                      preferred_element_type=jnp.float32)


def blocked_matvec_ref(W: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Exact logit matvec oracle: (n, d) @ (d,) -> (n,) in float32."""
    return jnp.dot(W, q, preferred_element_type=jnp.float32)


def fused_cascade_ref(V4, qb, flat, cols, *, n_arms: int, K: int,
                      vscale=None, qscale=None, codebook=None,
                      packed_int4=False, n_valid=None,
                      cert=None, k_cert=1):
    """Step-accurate numpy simulation of the fused cascade kernel.

    Walks the same FlatSchedule the kernel prefetches, one grid step at a
    time: pull -> accumulate, eliminate at round-end flags (tile-max means,
    iterative max-extraction with lowest-index tie-break), final top-K over
    the surviving arms.  Slow and deliberately naive — the point is that it
    shares no code with either the kernel or the `lax.scan` fallback.

    V4: (n_tiles, n_blocks, R, C); qb: (n_blocks, C); flat: FlatSchedule;
    cols: (t_dense + S,) column-block ids: the dense phase's, then one per
    step (i.e. perm[flat.col_pos]).
    With ``vscale (n_tiles, n_blocks)`` / ``qscale (n_blocks,)`` the
    operands are int8 and each pull is an exact integer dot dequantized by
    the scalar scale product (the quantized path, DESIGN.md §10);
    ``packed_int4=True`` marks the table nibble-packed (stored last dim
    C/2, half-split layout) and the oracle unpacks it with independent
    numpy bit arithmetic before the same exact integer dot.  ``codebook``
    ((n_blocks, S, n_codes, w) f32) selects the product-quantized tier
    instead: ``V4`` holds uint8 codes (last dim S), ``qb`` stays f32, and
    each pull is an independent numpy LUT walk.
    ``n_valid`` (default ``n_arms``) masks rows at or past it out of every
    ranking, like the kernel's scalar-prefetch bound.  With ``cert``
    (the (rounds+1, 2) coefficient array of
    `repro.core.schedule.cert_coeffs`) the adaptive early exit
    (DESIGN.md §12) is simulated too — running M2 accumulator,
    per-round-end certification of the top-``k_cert`` rows, frozen pulls,
    actual-pull-count normalization — and the return grows a third
    element ``rounds_used``.
    Returns (ids (K,), vals (K,)) — vals unscaled, like the kernel.
    """
    quantized = vscale is not None
    is_pq = codebook is not None
    adaptive = cert is not None
    if is_pq:
        V4 = np.asarray(V4, np.uint8)    # per-subspace code table
        qb = np.asarray(qb, np.float32)
        codebook = np.asarray(codebook, np.float32)
    elif quantized:
        if packed_int4:
            # Independent nibble unpack (half-split layout): byte k holds
            # column k in its low nibble and column k + C/2 in its high.
            pu = np.asarray(V4).astype(np.uint8)
            lo = (pu & 0x0F).astype(np.int32)
            lo = np.where(lo >= 8, lo - 16, lo)
            hi = (pu >> 4).astype(np.int32)
            hi = np.where(hi >= 8, hi - 16, hi)
            V4 = np.concatenate([lo, hi], axis=-1)
        else:
            V4 = np.asarray(V4, np.int32)   # exact integer tile-dots
        qb = np.asarray(qb, np.int32)
        vscale = np.asarray(vscale, np.float32)
        qscale = np.asarray(qscale, np.float32)
    else:
        V4 = np.asarray(V4, np.float32)
        qb = np.asarray(qb, np.float32)
    cols = np.asarray(cols)
    n_tiles, n_blocks, R, C = V4.shape
    if is_pq:
        S, w = codebook.shape[1], codebook.shape[3]
        C = S * w                       # true pull width (denominators)
    if n_valid is None:
        n_valid = n_arms
    acc = np.zeros((n_tiles, R), np.float32)
    acc2 = np.zeros((n_tiles, R), np.float32)
    surv = np.arange(n_tiles)
    if adaptive:
        cert = np.asarray(cert, np.float32)
        n_rounds = int(np.sum(np.asarray(flat.is_end)))
        active, t_stop, rounds_used, rnd = True, flat.t_final, n_rounds, 0

    def masked_means(tile, denom):
        rowids = tile * R + np.arange(R)
        return np.where(rowids < n_valid, acc[tile] / denom, -np.inf)

    def take_max(buf):
        """Kernel-exact extraction step: max over non-extracted entries,
        lowest-index tie-break; extracted slots are NaN so they can never
        tie again (lax.top_k's distinct-index semantics)."""
        m = np.max(np.where(np.isnan(buf), -np.inf, buf))
        a = int(np.argmax(buf == m))
        return a, np.float32(m)

    def pull(tile, col):
        if is_pq:
            cb = codebook[col]                          # (S, n_codes, w)
            lut = (qb[col].reshape(S, 1, w) * cb).sum(-1)
            codes = V4[tile, col]                       # (R, S) uint8
            part = np.stack([
                lut[np.arange(S), codes[r]].sum()
                for r in range(R)]).astype(np.float32)
        elif quantized:
            raw = V4[tile, col] @ qb[col]               # exact int32
            s = np.float32(vscale[tile, col]) * np.float32(qscale[col])
            part = raw.astype(np.float32) * s
        else:
            part = V4[tile, col] @ qb[col]
        acc[tile] = acc[tile] + part
        acc2[tile] = acc2[tile] + part * part

    # round 1's dense phase: every tile, columns in permutation order
    for j in range(flat.t_dense):
        for tile in range(flat.n_dense):
            pull(tile, int(cols[j]))
    for i in range(flat.n_steps):
        if flat.is_pull[i] and (not adaptive or active):
            pull(surv[flat.slot[i]], int(cols[flat.t_dense + i]))
        if flat.is_end[i]:
            T, keep = int(flat.n_surv[i]), int(flat.n_keep[i])
            denom = np.float32(int(flat.t_cum[i]) * C)
            scores = np.array([masked_means(surv[s], denom).max()
                               for s in range(T)], np.float32)
            new = []
            for _ in range(keep):
                a, _m = take_max(scores)        # first max == lowest index
                new.append(surv[a])
                scores[a] = np.nan
            surv = np.asarray(new)
            if adaptive and active:
                a_l, b_l = np.float32(cert[rnd, 0]), np.float32(cert[rnd, 1])
                denomC = np.float32(denom * np.float32(C))
                bufM, bufU, bufL = [], [], []
                for s in range(keep):
                    tile = surv[s]
                    mu = (acc[tile] / denom).astype(np.float32)
                    if a_l != 0.0:
                        v = (acc2[tile] / denomC - mu * mu).astype(
                            np.float32)
                        rad = a_l * np.sqrt(np.maximum(v, np.float32(0.0))
                                            ) + b_l
                    else:
                        rad = np.full_like(mu, b_l)
                    valid = tile * R + np.arange(R) < n_valid
                    bufM.append(np.where(valid, mu, -np.inf))
                    bufU.append(np.where(valid, mu + rad, -np.inf))
                    bufL.append(np.where(valid, mu - rad, -np.inf))
                bufM = np.concatenate(bufM).astype(np.float32)
                bufU = np.concatenate(bufU).astype(np.float32)
                bufL = np.concatenate(bufL).astype(np.float32)
                minlb = np.inf
                for _ in range(k_cert):
                    a, _m = take_max(bufM)      # lowest-index tie-break
                    minlb = min(minlb, bufL[a])
                    bufU[a] = -np.inf
                    bufM[a] = np.nan
                if minlb >= bufU.max():
                    active = False
                    t_stop = int(flat.t_cum[i])
                    rounds_used = rnd + 1
            if adaptive:
                rnd += 1

    t_fin = t_stop if adaptive else flat.t_final
    denom = np.float32(max(1, t_fin) * C)
    flat_scores = np.concatenate([masked_means(surv[s], denom)
                                  for s in range(flat.n_final)])
    ids, vals = [], []
    for _ in range(K):
        a, m = take_max(flat_scores)
        s, r = divmod(a, R)
        ids.append(surv[s] * R + r)
        vals.append(m)
        flat_scores[a] = np.nan
    out = (np.asarray(ids, np.int32), np.asarray(vals, np.float32))
    return (*out, rounds_used) if adaptive else out
