"""jit'd public wrappers for the Pallas kernels.

On non-TPU backends (this CPU container) the kernels run in interpret mode,
which executes the kernel body in Python for correctness validation; on TPU
they lower to Mosaic.  The pure-jnp oracles live in ``repro.kernels.ref``.

Every wrapper is generic in the feature-tile width ``C``: the same entry
points serve 'row'-mode plans (wide blocks) and 'coord'-mode plans (narrow
coordinate tiles, DESIGN.md §14) — callers select the pull mode purely
through the plan geometry baked into the operands and flat schedule.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.gather_dot import gather_block_dot_pallas
from repro.kernels.blocked_matvec import blocked_matvec_pallas
from repro.kernels.fused_cascade import (fused_cascade_pallas,
                                         fused_cascade_batched_pallas)
from repro.kernels import ref

__all__ = ["gather_block_dot", "blocked_matvec", "fused_cascade",
           "fused_cascade_batched", "on_tpu", "count_pallas_calls"]


def on_tpu() -> bool:
    """True when the default backend compiles Pallas to Mosaic (TPU)."""
    return jax.default_backend() == "tpu"


def count_pallas_calls(jaxpr) -> int:
    """Kernel dispatches reachable from ``jaxpr`` (through jit/scan/etc.).

    The PR-1 acceptance metric: the fused path must show exactly one
    `pallas_call` regardless of round count.  Shared by the test suite and
    `benchmarks/bench_fused.py` so the two can't drift apart.
    """
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def sub(params):
        for v in params.values():
            stack = [v]
            while stack:
                x = stack.pop()
                if isinstance(x, ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, Jaxpr):
                    yield x
                elif isinstance(x, (list, tuple)):
                    stack.extend(x)

    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
        for j in sub(eqn.params):
            total += count_pallas_calls(j)
    return total


def gather_block_dot(V4, idx, cols, qsel):
    """Per-round BoundedME pull step: see `repro.kernels.gather_dot`."""
    return gather_block_dot_pallas(V4, idx, cols, qsel,
                                   interpret=not on_tpu())


def fused_cascade(V4, qb, slotcode, rounds_meta, cols, *, n_arms, K,
                  t_final, n_final, t_dense, k_out=None, n_valid=None,
                  vscale=None, qscale=None, codebook=None,
                  packed_int4=False, cert=None, k_cert=1,
                  track_var=False):
    """Whole-cascade single dispatch: see `repro.kernels.fused_cascade`.

    ``t_dense`` is the flat schedule's dense round-1 column count
    (``cols`` holds those columns first).  Beyond the schedule operands:
    ``k_out`` (default K) widens the in-kernel final extraction so
    shard-local callers get extra threshold candidates (it never changes
    the elimination schedule; must satisfy ``K <= k_out <= n_final *
    tile``); ``n_valid`` (default ``n_arms``,
    may be a traced scalar) masks rows >= n_valid out of every tile-max
    and extraction so caller padding can never win (DESIGN.md §7);
    ``vscale``/``qscale`` are the int8/int4 dequantization scales of the
    quantized sampling path (DESIGN.md §10, `repro.core.quantize`) —
    ``packed_int4=True`` marks the table nibble-packed (last dim C/2) —
    and ``codebook`` selects the product-quantized tier instead (uint8
    code table, f32 queries, LUT tile-dots);
    ``cert``/``k_cert``/``track_var`` (per-round radius coefficients from
    `repro.core.schedule.cert_coeffs`, the certified top-K, and the
    M2-accumulator switch) enable adaptive early exit and append a
    ``rounds_used`` output (DESIGN.md §12).
    """
    return fused_cascade_pallas(V4, qb, slotcode, rounds_meta, cols,
                                n_arms=n_arms, K=K, t_final=t_final,
                                n_final=n_final, t_dense=t_dense,
                                k_out=k_out,
                                n_valid=n_valid, vscale=vscale,
                                qscale=qscale, codebook=codebook,
                                packed_int4=packed_int4, cert=cert,
                                k_cert=k_cert, track_var=track_var,
                                interpret=not on_tpu())


def fused_cascade_batched(V4, Qb, slotcode, rounds_meta, cols, *, n_arms, K,
                          t_final, n_final, t_dense, k_out=None,
                          n_valid=None,
                          vscale=None, qscale=None, codebook=None,
                          packed_int4=False, cert=None, k_cert=1,
                          track_var=False):
    """Batched whole-cascade dispatch: query axis in the kernel grid.

    ``k_out``/``n_valid``/``vscale``/``qscale``/``codebook``/
    ``packed_int4``/``cert`` behave exactly as in :func:`fused_cascade`
    (``qscale`` is per query here, (B, n_blocks), and the adaptive
    ``rounds_used`` output is per query, (B,)).
    """
    return fused_cascade_batched_pallas(V4, Qb, slotcode, rounds_meta, cols,
                                        n_arms=n_arms, K=K, t_final=t_final,
                                        n_final=n_final, t_dense=t_dense,
                                        k_out=k_out,
                                        n_valid=n_valid, vscale=vscale,
                                        qscale=qscale, codebook=codebook,
                                        packed_int4=packed_int4, cert=cert,
                                        k_cert=k_cert, track_var=track_var,
                                        interpret=not on_tpu())


def blocked_matvec(W, q, *, tile_n: int = 256, tile_d: int = 512):
    """Exact blocked logit matvec: see `repro.kernels.blocked_matvec`."""
    return blocked_matvec_pallas(W, q, tile_n=tile_n, tile_d=tile_d,
                                 interpret=not on_tpu())
