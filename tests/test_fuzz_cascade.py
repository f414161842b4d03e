"""Property-based differential fuzzer for the fused cascade (ISSUE 5+7+8).

Three independent implementations of the same flat-schedule program —
the Pallas kernel (interpret mode), the `lax.scan`/dense jnp fallback,
and the deliberately naive numpy oracle (`repro.kernels.ref`) — must
agree across randomized geometry: ragged n and N, K > tile, caller
padding via ``n_valid``, the full fp32/int8/int4/pq precision ladder
(ISSUE 8 — the oracle unpacks nibbles and walks pq LUTs with its own
independent numpy arithmetic), hoeffding/bernstein bound families,
adaptive on/off, widened ``k_out``, and (ISSUE 7) the pull mode —
'row', 'coord' (narrow coordinate tiles, including d not a multiple of
the feature-tile width) and 'hybrid' (whichever concrete mode the
dispatcher resolves must itself pass the trio check).

Agreement contract (the same one the PR-1/PR-3 suites pin):

  * kernel vs jnp fallback — **bitwise** on ids, scores and (adaptive)
    per-query ``rounds_used``;
  * kernel vs numpy oracle — ids and ``rounds_used`` exact, scores to
    tight float tolerance (numpy's BLAS matvec reduction order is not
    XLA's, so the accumulators differ in the last bits).

A fixed parametrized grid runs from a clean checkout (no hypothesis
needed); the hypothesis fuzzer on top randomizes the same space and is
skipped gracefully when hypothesis is absent (`optional_hypothesis`).
All comparisons use ``final_exact=False`` — the one configuration where
kernel and fallback are specified to be bitwise-identical (the
final-exact paths diverge by design: coverage completion vs dense
rescore).
"""

import jax
import numpy as np
import pytest

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.core.boundedme_jax import (_pad_operands, _quantize_table,
                                      _tile_major, bounded_me_decode,
                                      make_plan)
from repro.core.quantize import quantize_blocks
from repro.core.schedule import cert_coeffs, flatten_schedule
from repro.kernels.ref import fused_cascade_ref


def _oracle_decode(V, Q, key, plan, *, k_out, n_valid, adaptive):
    """Numpy-oracle mirror of `bounded_me_decode(final_exact=False)`."""
    import jax.numpy as jnp

    C = plan.block
    B = Q.shape[0]
    Vp, Qp = _pad_operands(jnp.asarray(V), jnp.asarray(Q), plan)
    V4 = _tile_major(Vp, plan)
    Qb = np.asarray(Qp).reshape(B, plan.n_blocks, C)
    perm = np.asarray(jax.random.permutation(key, plan.n_blocks))
    flat = flatten_schedule(plan.schedule, final_coverage=False)
    cols = perm[flat.col_pos]
    scale = np.float32((plan.n_blocks * C) / plan.N)
    cert = cert_coeffs(plan.schedule) if adaptive else None
    vscale = qscale = codebook = None
    packed_int4 = False
    if plan.precision in ("int8", "int4"):
        Vq, vscale = _quantize_table(V4, plan)
        Q8, qscale = quantize_blocks(jnp.asarray(Qb))
        V4, Qb = np.asarray(Vq), np.asarray(Q8)
        vscale, qscale = np.asarray(vscale), np.asarray(qscale)
        packed_int4 = plan.precision == "int4"
    elif plan.precision == "pq":
        # same deterministic trainer/encoder the kernel path uses — the
        # oracle sees the identical codes + codebook, queries stay fp32
        codes, cb = _quantize_table(V4, plan)
        V4, codebook = np.asarray(codes), np.asarray(cb)
    else:
        V4 = np.asarray(V4)
    ids, vals, rounds = [], [], []
    for b in range(B):
        out = fused_cascade_ref(
            V4, Qb[b], flat, cols, n_arms=plan.n, K=k_out,
            vscale=vscale, qscale=None if qscale is None else qscale[b],
            codebook=codebook, packed_int4=packed_int4,
            n_valid=n_valid, cert=cert, k_cert=plan.K)
        ids.append(out[0])
        vals.append(out[1] * scale)
        if adaptive:
            rounds.append(out[2])
    out = (np.stack(ids), np.stack(vals))
    return (*out, np.asarray(rounds, np.int32)) if adaptive else out


def _check_trio(n, N, K, tile, block, n_valid, precision, bound, adaptive,
                B, eps, widen_k_out, seed, pull_mode="row", coord_block=128):
    rng = np.random.default_rng(seed)
    V = rng.normal(size=(n, N)).astype(np.float32)
    Q = rng.normal(size=(B, N)).astype(np.float32)
    # pq refuses to guess a worst-case bound (DESIGN.md §10); the trio
    # contract only needs the *same* schedule on all three paths, so any
    # fixed value works — honesty of the bound is the guarantee suite's job
    quant_err = 0.05 if precision == "pq" else None
    plan = make_plan(n, N, K=K, eps=eps, delta=0.1, value_range=8.0,
                     tile=tile, block=block, precision=precision,
                     bound=bound, pull_mode=pull_mode,
                     coord_block=coord_block, quant_err=quant_err)
    assert plan.pull_mode in ("row", "coord")   # hybrid resolves concrete
    k_out = min(plan.K + 2, plan.k_out_cap) if widen_k_out else plan.K
    key = jax.random.PRNGKey(seed)
    kw = dict(plan=plan, final_exact=False, k_out=k_out, n_valid=n_valid,
              adaptive=adaptive)
    out_k = bounded_me_decode(V, Q, key, use_pallas=True, **kw)
    out_j = bounded_me_decode(V, Q, key, use_pallas=False, **kw)
    out_o = _oracle_decode(V, Q, key, plan, k_out=k_out, n_valid=n_valid,
                           adaptive=adaptive)
    tag = (n, N, K, tile, block, n_valid, precision, bound, adaptive, B)
    # kernel vs fallback: bitwise
    np.testing.assert_array_equal(np.asarray(out_k[0]), np.asarray(out_j[0]),
                                  err_msg=f"ids vs fallback {tag}")
    np.testing.assert_array_equal(np.asarray(out_k[1]), np.asarray(out_j[1]),
                                  err_msg=f"scores vs fallback {tag}")
    # kernel vs oracle: ids exact, scores to tight tolerance
    np.testing.assert_array_equal(np.asarray(out_k[0]), np.asarray(out_o[0]),
                                  err_msg=f"ids vs oracle {tag}")
    np.testing.assert_allclose(np.asarray(out_k[1]), np.asarray(out_o[1]),
                               rtol=2e-5, atol=1e-7,
                               err_msg=f"scores vs oracle {tag}")
    if adaptive:
        np.testing.assert_array_equal(np.asarray(out_k[2]),
                                      np.asarray(out_j[2]),
                                      err_msg=f"rounds vs fallback {tag}")
        np.testing.assert_array_equal(np.asarray(out_k[2]),
                                      np.asarray(out_o[2]),
                                      err_msg=f"rounds vs oracle {tag}")


# deterministic grid: runs from a clean checkout, covers every axis once
GRID = [
    # n,   N,    K, tile, blk, n_valid, precision, bound,      adapt, B
    (96,   512,  2, 8,    64,  96,      "fp32",    "hoeffding", False, 2),
    (96,   512,  2, 8,    64,  96,      "fp32",    "hoeffding", True,  2),
    (100,  700,  3, 8,    128, 87,      "fp32",    "bernstein", True,  1),
    (64,   384,  12, 4,   64,  64,      "fp32",    "hoeffding", True,  2),
    (96,   512,  2, 8,    64,  96,      "int8",    "hoeffding", True,  2),
    (77,   300,  4, 8,    32,  60,      "int8",    "bernstein", True,  3),
    (33,   257,  1, 8,    64,  33,      "fp32",    "bernstein", True,  1),
    (96,   512,  5, 8,    64,  3,       "fp32",    "hoeffding", True,  1),
    # ISSUE 8: sub-byte tiers through the identical trio contract —
    # nibble-packed int4 and LUT-walking pq, incl. ragged d (700, 257
    # are not multiples of the block, exercising the zero-padded tail)
    (96,   512,  2, 8,    64,  96,      "int4",    "hoeffding", True,  2),
    (100,  700,  3, 8,    128, 87,      "int4",    "bernstein", True,  1),
    (96,   512,  2, 8,    64,  96,      "pq",      "hoeffding", True,  2),
    (33,   257,  1, 8,    64,  33,      "pq",      "bernstein", True,  1),
]


@pytest.mark.parametrize(
    "n,N,K,tile,block,n_valid,precision,bound,adaptive,B", GRID)
def test_grid_kernel_fallback_oracle_bitwise(n, N, K, tile, block, n_valid,
                                             precision, bound, adaptive, B):
    _check_trio(n, N, K, tile, block, n_valid, precision, bound, adaptive,
                B, eps=0.7, widen_k_out=(K < n), seed=n + 7 * K)


# coordinate / hybrid pull modes (ISSUE 7) — same trio contract, narrow
# feature tiles; includes d NOT a multiple of the coord tile (700 % 128,
# 300 % 96, 257 % 64 != 0, exercising the zero-padded ragged last tile)
COORD_GRID = [
    # n,  N,   K, tile, cb,  n_valid, precision, bound,      adapt, B, mode
    (96,  512, 2, 8,    128, 96,  "fp32", "hoeffding", False, 2, "coord"),
    (100, 700, 3, 8,    128, 87,  "fp32", "bernstein", True,  1, "coord"),
    (96,  512, 2, 8,    128, 96,  "int8", "hoeffding", True,  2, "coord"),
    (77,  300, 4, 8,    96,  60,  "int8", "bernstein", True,  3, "coord"),
    (33,  257, 1, 8,    64,  33,  "fp32", "hoeffding", True,  1, "coord"),
    (96,  512, 2, 8,    128, 96,  "fp32", "hoeffding", False, 2, "hybrid"),
    (100, 700, 3, 8,    128, 87,  "int8", "hoeffding", True,  2, "hybrid"),
    # ISSUE 8: int4/pq under narrow coordinate tiles (coord_block is the
    # effective pull width — 96 % pq_subdims == 0, 64 even for nibbles)
    (96,  512, 2, 8,    128, 96,  "int4", "hoeffding", True,  2, "coord"),
    (77,  300, 4, 8,    96,  60,  "pq",   "bernstein", True,  3, "coord"),
    (100, 700, 3, 8,    128, 87,  "int4", "hoeffding", True,  2, "hybrid"),
    (96,  512, 2, 8,    64,  96,  "pq",   "hoeffding", True,  1, "hybrid"),
]


@pytest.mark.parametrize(
    "n,N,K,tile,cb,n_valid,precision,bound,adaptive,B,mode", COORD_GRID)
def test_coord_grid_kernel_fallback_oracle_bitwise(
        n, N, K, tile, cb, n_valid, precision, bound, adaptive, B, mode):
    # row block stays at 128 — the width envelope the bitwise contract has
    # always been pinned at (a hybrid resolving to 'row' then lands on the
    # same geometry the row GRID already certifies)
    _check_trio(n, N, K, tile, 128, n_valid, precision, bound, adaptive,
                B, eps=0.7, widen_k_out=(K < n), seed=n + 7 * K,
                pull_mode=mode, coord_block=cb)


def test_fewer_live_rows_than_k_out_no_duplicates():
    """Regression for a pre-existing kernel bug this fuzzer surfaced: with
    fewer live rows than ``keep``/``k_out`` the in-kernel extraction's
    ``-inf`` markers tied with the exhausted maximum and re-extracted the
    same slot — duplicating winners (which carry ids < n_valid and so
    would survive the sharded merge's filler mask) and silently dropping
    valid rows.  Extraction now uses NaN markers (lax.top_k's
    distinct-index semantics): every valid row appears exactly once and
    filler slots carry -inf scores."""
    rng = np.random.default_rng(0)
    V = rng.normal(size=(96, 512)).astype(np.float32)
    Q = rng.normal(size=(2, 512)).astype(np.float32)
    plan = make_plan(96, 512, K=5, eps=0.7, delta=0.1, value_range=8.0,
                     block=64)
    key = jax.random.PRNGKey(3)
    n_live = 3
    for adaptive in (False, True):
        kw = dict(plan=plan, final_exact=False, k_out=7, n_valid=n_live,
                  adaptive=adaptive)
        out_k = bounded_me_decode(V, Q, key, use_pallas=True, **kw)
        out_j = bounded_me_decode(V, Q, key, use_pallas=False, **kw)
        np.testing.assert_array_equal(np.asarray(out_k[0]),
                                      np.asarray(out_j[0]))
        np.testing.assert_array_equal(np.asarray(out_k[1]),
                                      np.asarray(out_j[1]))
        ids = np.asarray(out_k[0])
        scores = np.asarray(out_k[1])
        for b in range(2):
            live = ids[b][scores[b] > -np.inf]
            assert sorted(live.tolist()) == list(range(n_live)), adaptive
            assert np.all(scores[b][n_live:] == -np.inf), adaptive


@given(st.data())
@settings(max_examples=12, deadline=None, derandomize=True)
def test_fuzz_kernel_fallback_oracle_bitwise(data):
    n = data.draw(st.integers(10, 160), label="n")
    N = data.draw(st.integers(64, 1200), label="N")
    K = data.draw(st.integers(1, min(5, n)), label="K")
    tile = data.draw(st.sampled_from([4, 8]), label="tile")
    block = data.draw(st.sampled_from([32, 64, 128]), label="block")
    n_valid = data.draw(st.integers(1, n), label="n_valid")
    pull_mode = data.draw(st.sampled_from(["row", "coord", "hybrid"]),
                          label="pull_mode")
    coord_block = data.draw(st.sampled_from([32, 64, 96, 128]),
                            label="coord_block")
    # make_plan refuses int4 on an odd pull width and pq on one that the
    # 8-wide subspaces do not divide (a block clamps to N when N is
    # smaller): draw only tiers every pull width of this geometry holds
    widths = {"row": [min(block, N)], "coord": [min(coord_block, N)]}
    widths["hybrid"] = widths["row"] + widths["coord"]
    tiers = ["fp32", "int8"]
    if all(w % 2 == 0 for w in widths[pull_mode]):
        tiers.append("int4")
    if all(w % 8 == 0 for w in widths[pull_mode]):
        tiers.append("pq")
    precision = data.draw(st.sampled_from(tiers), label="precision")
    bound = data.draw(st.sampled_from(["hoeffding", "bernstein"]),
                      label="bound")
    adaptive = data.draw(st.booleans(), label="adaptive")
    B = data.draw(st.integers(1, 2), label="B")
    eps = data.draw(st.sampled_from([0.4, 0.8, 1.6]), label="eps")
    widen = data.draw(st.booleans(), label="widen_k_out")
    seed = data.draw(st.integers(0, 2**16), label="seed")
    _check_trio(n, N, K, tile, block, n_valid, precision, bound, adaptive,
                B, eps=eps, widen_k_out=widen, seed=seed,
                pull_mode=pull_mode, coord_block=coord_block)