"""Plain exact top-K of the served queries, and the lower-precision control.

The reference is a straightforward ``Q @ V.T`` in fp32 at
``Precision.HIGHEST`` and ``lax.top_k``, computed on the chip in blocks of
queries over a table regenerated from the seed (`bench.loadgen.make_table`):
it imports nothing of the program and reads nothing the program made.
Scores are mean products ``(q . v) / d``, the unit the program returns.

A table stored in bf16 (``table_dtype``) is widened to fp32 before any
product.  The widening is exact, so the reference scores the stored
values with exact fp32 products.  Over a bf16 table a single bf16 pass
(queries rounded to bf16) fails the check's ``score_rel_err`` limit of
2e-6; a computation that splits the query into two or three bf16 parts
(``high``, ``xla_high``) does not, since a bf16 row has no low part to
drop: the limit tells fp32 products of the stored rows from one bf16
pass only.

The control is the same top-K in the program's place, computed one step
below what the configuration states.  Over an fp32 table that is fp32
products as ``Precision.HIGH`` makes them, three bf16 passes.  They are
written out as three bf16 dots, each behind an optimization barrier, so
that it computes alike on a CPU (whose backend ignores
``Precision.HIGH``) and on a TPU (whose compiler would otherwise fold the
passes into fewer); ``xla_high`` is XLA's own ``Precision.HIGH``, read
beside it on the chip.  Over a bf16 table, whose rows split into no
second bf16 pass, the step below is ``bf16``: one bf16 pass with the
queries rounded to bf16, what a plain 16-bit matmul computes.  `CONTROL`
maps each ``table_dtype`` to its control.
"""

from __future__ import annotations

import numpy as np

BLOCK = 256     # queries per reference block

#: the control of each ``table_dtype``: the step below how it is stored
CONTROL = {"float32": "high", "bfloat16": "bf16"}


def _scores(V, Q, precision: str):
    import jax
    import jax.numpy as jnp

    if precision == "bf16":
        s = jnp.dot(Q.astype(jnp.bfloat16), V.astype(jnp.bfloat16).T,
                    preferred_element_type=jnp.float32)
        return s / jnp.float32(V.shape[1])
    V = V.astype(jnp.float32)           # exact from bf16
    if precision == "highest":
        s = jnp.dot(Q, V.T, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    elif precision == "high":
        # x = hi + lo with hi the top 16 bits of x (exact in bf16) and lo
        # rounded to bf16; hi.hi + hi.lo + lo.hi in f32.  The split is by
        # bit mask, which no compiler folds away as an f32 -> bf16 -> f32
        # round trip.
        def split(x):
            bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
            hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                              jnp.float32)
            return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)

        def dot(a, b):
            return jax.lax.optimization_barrier(
                jnp.dot(a, b.T, preferred_element_type=jnp.float32))

        qh, ql = split(Q)
        vh, vl = split(V)
        s = dot(qh, vh) + (dot(qh, vl) + dot(ql, vh))
    elif precision == "xla_high":
        s = jnp.dot(Q, V.T, precision=jax.lax.Precision.HIGH,
                    preferred_element_type=jnp.float32)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return s / jnp.float32(V.shape[1])


def exact_answers(table, n_valid: int, Q: np.ndarray, ids: np.ndarray,
                  K: int):
    """Exact top-K of ``Q`` and the exact scores of served ``ids``.

    Returns ``(top_ids (B, K), top_scores (B, K), served_exact (B, Ks))``
    as host arrays.  The top-K is fp32 at ``HIGHEST`` on the chip; the
    served rows are gathered and scored on the host in float64, so their
    exact scores carry no fp32 rounding of their own.  Ids at or past
    ``n_valid`` score ``nan``.
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(V, Qb, idb):
        s = _scores(V[:n_valid], Qb, "highest")
        top, pos = jax.lax.top_k(s, K)
        return pos, top, V[jnp.clip(idb, 0, n_valid - 1)].astype(
            jnp.float32)

    tops, vals, got = [], [], []
    for i in range(0, len(Q), BLOCK):
        pos, top, rows = block(table, jnp.asarray(Q[i:i + BLOCK]),
                               jnp.asarray(ids[i:i + BLOCK]))
        tops.append(np.asarray(pos))
        vals.append(np.asarray(top))
        q64 = Q[i:i + BLOCK].astype(np.float64)
        got.append(np.einsum("bkd,bd->bk", np.asarray(rows, np.float64),
                             q64) / Q.shape[1])
    if not tops:
        z = np.zeros((0, K))
        return z.astype(np.int32), z, np.zeros((0, ids.shape[1]))
    served = np.concatenate(got)
    served[(ids < 0) | (ids >= n_valid)] = np.nan
    return np.concatenate(tops), np.concatenate(vals), served


def control_answers(table, n_valid: int, Q: np.ndarray, K: int,
                    precision: str = "high", rows=None):
    """Top-K ids and scores of ``Q``: the control at ``Precision.HIGH``.

    ``precision`` is ``"high"`` (three explicit bf16 passes),
    ``"xla_high"`` (XLA's own ``Precision.HIGH``) or ``"bf16"`` (one
    bf16 pass, the control of a bf16 table).

    ``precision="highest"`` with ``rows`` (start, stop), a slice of the
    table, is the reference with "the exchange between chips left out":
    only one shard's rows are searched.
    """
    import jax
    import jax.numpy as jnp

    lo, hi = (0, n_valid) if rows is None else rows

    @jax.jit
    def block(V, Qb):
        s = _scores(V[lo:hi], Qb, precision)
        top, pos = jax.lax.top_k(s, K)
        return pos + lo, top

    outs = [block(table, jnp.asarray(Q[i:i + BLOCK]))
            for i in range(0, len(Q), BLOCK)]
    return (np.concatenate([np.asarray(o[0]) for o in outs]).astype(np.int32),
            np.concatenate([np.asarray(o[1]) for o in outs]))
