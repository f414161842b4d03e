"""The int8 quantized sampling cascade (ISSUE 3 tentpole).

Covers the acceptance criteria that must hold from a clean checkout:

  * bit-exactness of the quantized fused kernel vs the jnp fallback in
    interpret mode (single query, batched decode), and vs the
    step-accurate numpy oracle;
  * the (eps, delta) guarantee survives quantization — empirical recall
    regression at int8 incl. exact top-K recovery at tiny eps;
  * adversarial extreme-scale tiles (one huge-magnitude row per tile):
    per-tile scales keep ranking intact;
  * the widened confidence bounds: pull counts grow monotonically with
    quant_err and `eps_effective` degrades gracefully;
  * fp32-exact final rescore on the int8 path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bounds
from repro.core.boundedme_jax import (bounded_me_blocked, bounded_me_decode,
                                      make_plan)
from repro.core.quantize import (INT4_LEVELS, INT8_LEVELS, pack_int4,
                                 pq_decode, pq_encode, pq_train,
                                 quantize_blocks, quantize_tiles,
                                 quantize_tiles_int4, unpack_int4)
from repro.core.schedule import make_schedule


def _data(n, N, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, N)).astype(np.float32),
            rng.normal(size=N).astype(np.float32))


class TestQuantizers:
    def test_tile_roundtrip_error_bound(self):
        rng = np.random.default_rng(0)
        V4 = jnp.asarray(rng.normal(size=(4, 3, 8, 64)), jnp.float32)
        V8, vscale = quantize_tiles(V4)
        assert V8.dtype == jnp.int8 and vscale.shape == (4, 3)
        recon = np.asarray(V8, np.float32) * np.asarray(vscale)[:, :, None,
                                                               None]
        err = np.abs(recon - np.asarray(V4))
        # round-to-nearest: per-entry error <= scale / 2
        assert np.all(err <= np.asarray(vscale)[:, :, None, None] / 2 + 1e-7)

    def test_zero_tile_gets_scale_one(self):
        V4 = jnp.zeros((2, 2, 8, 64), jnp.float32)
        V8, vscale = quantize_tiles(V4)
        np.testing.assert_array_equal(np.asarray(vscale), 1.0)
        np.testing.assert_array_equal(np.asarray(V8), 0)

    def test_query_blocks_batched_scales(self):
        rng = np.random.default_rng(1)
        Qb = jnp.asarray(rng.normal(size=(3, 5, 64)), jnp.float32)
        q8, qscale = quantize_blocks(Qb)
        assert q8.dtype == jnp.int8 and qscale.shape == (3, 5)
        assert int(np.abs(np.asarray(q8)).max()) <= INT8_LEVELS


class TestQuantizationErrorBound:
    def test_formula_and_monotonicity(self):
        e8 = bounds.quantization_error(8.0, bits=8)
        assert e8 == pytest.approx(4.0 * (1 / 127 + 1 / (4 * 127 ** 2)))
        assert bounds.quantization_error(8.0, bits=16) < e8  # more bits
        assert bounds.quantization_error(16.0) > e8          # wider range
        with pytest.raises(ValueError):
            bounds.quantization_error(0.0)

    def test_schedule_widens_with_quant_err(self):
        base = make_schedule(64, 128, K=2, eps=0.2, delta=0.1,
                             value_range=0.5)
        wide = make_schedule(64, 128, K=2, eps=0.2, delta=0.1,
                             value_range=0.5, quant_err=0.01)
        assert wide.quant_err == 0.01
        for rb, rw in zip(base.rounds, wide.rounds):
            assert rw.t_cum >= rb.t_cum     # never fewer pulls
        assert wide.total_pulls > base.total_pulls

    def test_unabsorbable_bias_saturates_to_full_coverage(self):
        # quant_err >= eps_1/2 on every round: all pulls go to N
        sched = make_schedule(64, 128, K=2, eps=0.2, delta=0.1,
                              value_range=0.5, quant_err=1.0)
        assert all(r.t_cum == 128 for r in sched.rounds)

    def test_eps_effective(self):
        base = make_schedule(64, 128, K=2, eps=0.2, delta=0.1,
                             value_range=0.5)
        assert base.eps_effective == base.eps
        wide = make_schedule(64, 128, K=2, eps=0.2, delta=0.1,
                             value_range=0.5, quant_err=1e-4)
        # tiny bias: every round absorbs it, no penalty
        assert wide.eps_effective == pytest.approx(wide.eps)
        bad = make_schedule(64, 128, K=2, eps=0.2, delta=0.1,
                            value_range=0.5, quant_err=0.05)
        assert bad.eps_effective > bad.eps

    def test_plan_precision_validation(self):
        with pytest.raises(ValueError):
            make_plan(64, 256, precision="int2")
        plan = make_plan(64, 256, K=1, eps=0.2, value_range=8.0, block=64,
                         precision="int8")
        assert plan.precision == "int8" and plan.quant_err > 0
        assert plan.eps_effective >= plan.schedule.eps
        fp = make_plan(64, 256, K=1, eps=0.2, value_range=8.0, block=64)
        assert fp.quant_err == 0.0 and fp.eps_effective == fp.schedule.eps


class TestCodecs:
    """Property tests for the PR-8 int4/pq codecs (ISSUE 8 satellite)."""

    def test_int4_pack_unpack_roundtrip_identity(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(-INT4_LEVELS, INT4_LEVELS + 1,
                                     size=(3, 4, 8, 64)), jnp.int8)
        np.testing.assert_array_equal(np.asarray(unpack_int4(pack_int4(x))),
                                      np.asarray(x))
        # packed layout: half the stored width, one byte per value pair
        assert pack_int4(x).shape == (3, 4, 8, 32)

    def test_int4_quantize_tiles_bounds_and_reconstruction(self):
        rng = np.random.default_rng(1)
        V4 = jnp.asarray(rng.normal(size=(4, 3, 8, 64)), jnp.float32)
        P4, vscale = quantize_tiles_int4(V4)
        assert P4.shape == (4, 3, 8, 32) and vscale.shape == (4, 3)
        codes = np.asarray(unpack_int4(P4))
        assert np.abs(codes).max() <= INT4_LEVELS
        recon = codes.astype(np.float32) * np.asarray(vscale)[:, :, None,
                                                              None]
        err = np.abs(recon - np.asarray(V4))
        # round-to-nearest on the 15-level grid: error <= scale / 2
        assert np.all(err <= np.asarray(vscale)[:, :, None, None] / 2 + 1e-6)

    def test_pq_assignment_is_argmin_distance(self):
        rng = np.random.default_rng(2)
        V4 = jnp.asarray(rng.normal(size=(2, 3, 8, 32)), jnp.float32)
        cb = pq_train(V4, n_codes=8, subdims=8)
        codes = np.asarray(pq_encode(V4, cb))
        X = np.asarray(V4).reshape(2, 3, 8, 4, 8)        # (T, Bn, R, S, w)
        C = np.asarray(cb)                                # (Bn, S, K, w)
        for t in range(2):
            for b in range(3):
                for r in range(8):
                    for s in range(4):
                        d = ((X[t, b, r, s][None] - C[b, s]) ** 2).sum(-1)
                        assert d[codes[t, b, r, s]] <= d.min() + 1e-5

    def test_pq_codebook_determinism(self):
        rng = np.random.default_rng(3)
        V4 = jnp.asarray(rng.normal(size=(2, 2, 8, 64)), jnp.float32)
        cb1 = pq_train(V4, n_codes=16, subdims=8)
        cb2 = pq_train(V4, n_codes=16, subdims=8)
        np.testing.assert_array_equal(np.asarray(cb1), np.asarray(cb2))
        np.testing.assert_array_equal(np.asarray(pq_encode(V4, cb1)),
                                      np.asarray(pq_encode(V4, cb2)))

    def test_pq_reconstruction_error_monotone_in_subdims(self):
        """Wider subspaces = fewer codebook cells per coordinate = coarser
        reconstruction; mean-squared error must not improve as subdims
        grows (same code budget spread over more dimensions)."""
        rng = np.random.default_rng(4)
        V4 = jnp.asarray(rng.normal(size=(3, 2, 8, 64)), jnp.float32)
        errs = []
        for w in (4, 8, 16):
            cb = pq_train(V4, n_codes=16, subdims=w)
            recon = pq_decode(pq_encode(V4, cb), cb)
            errs.append(float(jnp.mean((recon - V4) ** 2)))
        assert errs[0] <= errs[1] <= errs[2], errs

    def test_pq_shape_validation(self):
        V4 = jnp.zeros((1, 1, 8, 30), jnp.float32)
        with pytest.raises(ValueError):
            pq_train(V4, n_codes=8, subdims=8)    # 30 % 8 != 0
        with pytest.raises(ValueError):
            pq_train(jnp.zeros((1, 1, 8, 32), jnp.float32), n_codes=300,
                     subdims=8)                   # codes don't fit uint8


class TestBitExactness:
    """Kernel (interpret mode) vs jnp fallback vs numpy oracle, int8."""

    @pytest.mark.parametrize("n,N,tile,block,K", [
        (512, 2048, 8, 128, 3),
        (517, 2100, 8, 256, 12),     # ragged + K > tile
        (123, 300, 8, 64, 5),
    ])
    def test_fused_matches_fallback_bitwise(self, n, N, tile, block, K):
        V, q = _data(n, N, seed=n)
        kw = dict(K=K, eps=0.25, delta=0.1, value_range=8.0, tile=tile,
                  block=block, precision="int8")
        i_f, s_f, _ = bounded_me_blocked(V, q, jax.random.PRNGKey(7),
                                         use_pallas=True, **kw)
        i_j, s_j, _ = bounded_me_blocked(V, q, jax.random.PRNGKey(7),
                                         use_pallas=False, **kw)
        np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_j))
        np.testing.assert_array_equal(np.asarray(s_f), np.asarray(s_j))

    # round 1 in the kernel's dense phase, at shapes whose last tile group
    # is short: 64 int8 tiles a step (800 rows), 128 nibble-packed int4
    # tiles (1,100 rows), 256 pq code tiles (2,100 rows); eps 1.5 leaves
    # blocks to later rounds under the widened quantized bounds
    @pytest.mark.parametrize("mode", ["fixed", "hoeffding", "bernstein"])
    @pytest.mark.parametrize("layout", ["shared", "per_query"])
    @pytest.mark.parametrize("tier,n", [("int8", 800), ("int4", 1100),
                                        ("pq", 2100)])
    def test_dense_phase_tiers_bitwise(self, tier, n, layout, mode):
        from test_kernels import dense_parity

        dense_parity(layout, 3, mode, precision=tier, n=n, eps=1.5)

    def test_fused_matches_numpy_oracle(self):
        from repro.core.boundedme_jax import _pad_operands, _tile_major
        from repro.core.schedule import flatten_schedule
        from repro.kernels import ref
        from repro.kernels.fused_cascade import fused_cascade_pallas

        n, N, K, tile, block = 300, 900, 4, 8, 128
        V, q = _data(n, N, seed=2)
        plan = make_plan(n, N, K=K, eps=0.2, delta=0.1, value_range=8.0,
                         tile=tile, block=block, precision="int8")
        Vp, qp = _pad_operands(jnp.asarray(V), jnp.asarray(q), plan)
        V4 = _tile_major(Vp, plan)
        qb = qp.reshape(plan.n_blocks, plan.block)
        V8, vscale = quantize_tiles(V4)
        q8, qscale = quantize_blocks(qb)
        perm = jax.random.permutation(jax.random.PRNGKey(5), plan.n_blocks)
        flat = flatten_schedule(plan.schedule)
        cols = np.asarray(perm)[flat.col_pos]
        slotcode, rmeta = flat.packed()
        ids_k, vals_k = fused_cascade_pallas(
            V8, q8, jnp.asarray(slotcode), jnp.asarray(rmeta),
            jnp.asarray(cols), n_arms=plan.n, K=plan.K,
            t_final=flat.t_final, n_final=flat.n_final,
            t_dense=flat.t_dense, vscale=vscale, qscale=qscale,
            interpret=True)
        ids_o, vals_o = ref.fused_cascade_ref(
            V8, q8, flat, cols, n_arms=plan.n, K=plan.K,
            vscale=vscale, qscale=qscale)
        np.testing.assert_array_equal(np.asarray(ids_k), ids_o)
        np.testing.assert_allclose(np.asarray(vals_k), vals_o,
                                   rtol=1e-6, atol=1e-7)

    def test_decode_batched_bitwise_and_rescored(self):
        V, q = _data(256, 1024, seed=5)
        Q = np.stack([q, -q, 0.3 * q, _data(1, 1024, seed=9)[1]])
        plan = make_plan(256, 1024, K=2, eps=0.2, delta=0.1, value_range=8.0,
                         block=128, precision="int8")
        key = jax.random.PRNGKey(11)
        for fe in (False, True):
            ip, sp = bounded_me_decode(V, Q, key, plan=plan,
                                       final_exact=fe, use_pallas=True)
            ij, sj = bounded_me_decode(V, Q, key, plan=plan,
                                       final_exact=fe, use_pallas=False)
            np.testing.assert_array_equal(np.asarray(ip), np.asarray(ij))
            np.testing.assert_array_equal(np.asarray(sp), np.asarray(sj))
        # final_exact scores are fp32-exact mean products, no quant error
        for b in range(Q.shape[0]):
            for i, s in zip(np.asarray(ip)[b], np.asarray(sp)[b]):
                assert abs(s - float(V[i] @ Q[b]) / 1024.0) < 1e-5

    def test_int8_cascade_still_one_dispatch(self):
        """Quantization must not cost extra kernel launches: the whole int8
        cascade (quantize + pulls + eliminations + extraction) lowers to
        exactly one pallas_call, like the fp32 path."""
        from repro.core.boundedme_jax import _run_blocked
        from repro.kernels import ops

        plan = make_plan(512, 2048, K=3, eps=0.3, delta=0.1, value_range=8.0,
                         tile=8, block=128, precision="int8")
        assert len(plan.schedule.rounds) >= 3
        rng = np.random.default_rng(0)
        V = jnp.asarray(rng.normal(size=(512, 2048)), jnp.float32)
        q = jnp.asarray(rng.normal(size=2048), jnp.float32)

        def fused(V, q, k):
            return _run_blocked(V, q, k, plan=plan, use_pallas=True)

        jaxpr = jax.make_jaxpr(fused)(V, q, jax.random.PRNGKey(0))
        assert ops.count_pallas_calls(jaxpr.jaxpr) == 1

    def test_mismatched_scales_raise(self):
        from repro.kernels.fused_cascade import fused_cascade_pallas

        with pytest.raises(ValueError):
            fused_cascade_pallas(
                jnp.zeros((1, 1, 8, 128), jnp.int8),
                jnp.zeros((1, 128), jnp.int8),
                jnp.zeros((1,), jnp.int32), jnp.zeros((1, 3), jnp.int32),
                jnp.zeros((1,), jnp.int32), n_arms=8, K=1, t_final=1,
                n_final=1, t_dense=0, vscale=jnp.ones((1, 1)),
                interpret=True)


class TestRecallRegression:
    """(eps, delta) holds empirically at int8 (the widened-bounds check)."""

    def test_tiny_eps_recovers_planted_topk(self):
        """Exact top-K recovery at tiny eps, with winner margins above the
        irreducible int8 bias (~plan.quant_err per estimate).  int8 cannot
        separate arms closer than that — `eps_effective` floors at
        ~2*quant_err per saturated round, which is the honest contract the
        moderate-eps test below checks on unplanted data."""
        n, N, K, B = 1024, 2048, 3, 5
        V, _ = _data(n, N, seed=6)
        rng = np.random.default_rng(7)
        Q = rng.normal(size=(B, N)).astype(np.float32)
        V *= 0.2                       # noise scores well under the plants
        for b in range(B):             # per-query planted winners, spaced
            unit = Q[b] / np.linalg.norm(Q[b])
            for j in range(K):
                V[17 * b + j] = (4.0 + 0.5 * j) * unit
        plan = make_plan(n, N, K=K, eps=1e-4, delta=0.05,
                         value_range=8.0, block=256, precision="int8")
        ids, _ = bounded_me_decode(V, Q, jax.random.PRNGKey(0), plan=plan,
                                   final_exact=True, use_pallas=False)
        truth = np.argsort(-(V @ Q.T), axis=0)[:K].T
        for b in range(B):
            assert (set(np.asarray(ids)[b].tolist())
                    == set(truth[b].tolist())), b

    def test_moderate_eps_recall_floor(self):
        """int8 at eps=0.1 must stay within the guarantee: every returned
        arm is eps_effective-optimal on the mean-product scale."""
        n, N, K, B = 2048, 1024, 4, 8
        V, _ = _data(n, N, seed=8)
        rng = np.random.default_rng(9)
        Q = rng.normal(size=(B, N)).astype(np.float32)
        plan = make_plan(n, N, K=K, eps=0.1, delta=0.05, value_range=8.0,
                         block=256, precision="int8")
        ids, scores = bounded_me_decode(V, Q, jax.random.PRNGKey(1),
                                        plan=plan, final_exact=True,
                                        use_pallas=False)
        exact = (V @ Q.T).T / N                                # (B, n)
        kth_best = -np.sort(-exact, axis=1)[:, K - 1]          # (B,)
        eps_eff = plan.eps_effective
        for b in range(B):
            for s in np.asarray(scores)[b]:
                assert s >= kth_best[b] - eps_eff, (b, s, kth_best[b])

    def test_int8_vs_fp32_same_winners_at_small_eps(self):
        V, q = _data(512, 1024, seed=10)
        kw = dict(K=3, eps=1e-3, delta=0.05, value_range=8.0, block=128,
                  final_exact=True)
        i8, s8, _ = bounded_me_blocked(V, q, jax.random.PRNGKey(2),
                                       precision="int8", **kw)
        i32, s32, _ = bounded_me_blocked(V, q, jax.random.PRNGKey(2),
                                         precision="fp32", **kw)
        np.testing.assert_array_equal(np.asarray(i8), np.asarray(i32))
        np.testing.assert_allclose(np.asarray(s8), np.asarray(s32),
                                   rtol=1e-5, atol=1e-6)


class TestAdversarialScaleTiles:
    def test_one_huge_row_per_tile(self):
        """One huge-magnitude row per tile: per-tile symmetric scales keep
        every tile's winner representable, and the fp32 rescore returns
        exact scores.  A global (per-table) scale would quantize the noise
        rows to zero and still pass; the point is the huge rows must not
        poison each *other's* ranking."""
        n, N, K, tile, block = 128, 512, 5, 8, 64
        rng = np.random.default_rng(42)
        V = (0.01 * rng.normal(size=(n, N))).astype(np.float32)
        q = np.ones(N, np.float32)
        n_tiles = n // tile
        # distinct huge magnitudes, one per tile, winners = the K largest
        mags = 50.0 + np.arange(n_tiles, dtype=np.float32)
        for t in range(n_tiles):
            V[t * tile + (t % tile)] = mags[t] * 0.01
        ids, scores, plan = bounded_me_blocked(
            V, q, jax.random.PRNGKey(0), K=K, eps=1e-4, delta=0.05,
            value_range=8.0, tile=tile, block=block, final_exact=True,
            precision="int8")
        expect = {t * tile + (t % tile)
                  for t in range(n_tiles - K, n_tiles)}
        assert set(np.asarray(ids).tolist()) == expect
        for i, s in zip(np.asarray(ids), np.asarray(scores)):
            # fp32-exact up to accumulation order (sums are O(300) here)
            assert abs(s - float(V[i] @ q) / N) < 1e-4

    def test_huge_negative_row_does_not_crush_tilemate(self):
        """A huge-|value| row coarsens its tile's scale; the widened bounds
        plus the fp32 rescore must still surface a moderate winner sharing
        that tile."""
        n, N, tile, block = 64, 512, 8, 64
        rng = np.random.default_rng(3)
        V = (0.001 * rng.normal(size=(n, N))).astype(np.float32)
        V[0] = -100.0 * np.abs(rng.normal(size=N)).astype(np.float32)
        winner = rng.normal(size=N).astype(np.float32)
        V[1] = winner          # same tile as the huge-magnitude row
        q = winner / np.linalg.norm(winner)
        ids, _, _ = bounded_me_blocked(
            V, q, jax.random.PRNGKey(1), K=1, eps=1e-4, delta=0.05,
            value_range=8.0, tile=tile, block=block, final_exact=True,
            precision="int8")
        assert int(np.asarray(ids)[0]) == 1
