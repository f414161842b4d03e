"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Shape/dtype sweeps per the deliverable: each kernel asserted allclose
against ref.py across tile geometries and dtypes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.gather_dot import gather_block_dot_pallas
from repro.kernels.blocked_matvec import blocked_matvec_pallas


def _v4(n_tiles, n_blocks, R, C, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n_tiles, n_blocks, R, C)).astype(dtype)


class TestGatherBlockDot:
    @pytest.mark.parametrize("R,C", [(8, 128), (8, 512), (4, 256), (16, 128)])
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_allclose_vs_ref(self, R, C, dtype):
        rng = np.random.default_rng(1)
        V4 = jnp.asarray(_v4(12, 10, R, C, np.float32)).astype(dtype)
        idx = jnp.asarray(rng.permutation(12)[:5], jnp.int32)
        cols = jnp.asarray(rng.permutation(10)[:4], jnp.int32)
        qsel = jnp.asarray(rng.normal(size=(4, C)), dtype)
        out = gather_block_dot_pallas(V4, idx, cols, qsel, interpret=True)
        exp = ref.gather_block_dot_ref(V4, idx, cols, qsel)
        # f32 tol leaves headroom for accumulation-order differences between
        # the kernel's per-block adds and the fused einsum contraction
        tol = 1e-4 if dtype == np.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=tol, atol=tol)
        assert out.dtype == jnp.float32  # f32 accumulation always

    def test_single_block_single_tile(self):
        V4 = jnp.asarray(_v4(1, 1, 8, 128, np.float32))
        idx = jnp.zeros((1,), jnp.int32)
        cols = jnp.zeros((1,), jnp.int32)
        qsel = jnp.ones((1, 128), jnp.float32)
        out = gather_block_dot_pallas(V4, idx, cols, qsel, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(V4[0, 0].sum(-1))[None],
                                   rtol=1e-5)

    def test_duplicate_gather_indices(self):
        """The same tile/block may be addressed twice (stress index_map)."""
        V4 = jnp.asarray(_v4(4, 4, 8, 128, np.float32))
        idx = jnp.asarray([2, 2, 0], jnp.int32)
        cols = jnp.asarray([1, 1], jnp.int32)
        qsel = jnp.asarray(np.random.default_rng(0).normal(size=(2, 128)),
                           jnp.float32)
        out = gather_block_dot_pallas(V4, idx, cols, qsel, interpret=True)
        exp = ref.gather_block_dot_ref(V4, idx, cols, qsel)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5)


class TestBlockedMatvec:
    @pytest.mark.parametrize("n,d,tn,td", [(512, 1024, 256, 512),
                                           (256, 512, 128, 128),
                                           (1024, 2048, 256, 1024)])
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_allclose_vs_ref(self, n, d, tn, td, dtype):
        rng = np.random.default_rng(2)
        W = jnp.asarray(rng.normal(size=(n, d)), dtype)
        q = jnp.asarray(rng.normal(size=d), dtype)
        out = blocked_matvec_pallas(W, q, tile_n=tn, tile_d=td,
                                    interpret=True)
        exp = ref.blocked_matvec_ref(W, q)
        tol = 1e-4 if dtype == np.float32 else 0.3
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=tol, atol=tol)

    def test_indivisible_raises(self):
        W = jnp.zeros((100, 512))
        q = jnp.zeros((512,))
        with pytest.raises(ValueError):
            blocked_matvec_pallas(W, q, tile_n=64, tile_d=512,
                                  interpret=True)


def _fused_setup(n, N, K, tile, block, eps=0.2, seed=0, final_exact=False):
    """Pad + tile a random instance and flatten its schedule."""
    from repro.core.boundedme_jax import (_pad_operands, _tile_major,
                                          make_plan)
    from repro.core.schedule import flatten_schedule

    rng = np.random.default_rng(seed)
    V = rng.normal(size=(n, N)).astype(np.float32)
    q = rng.normal(size=N).astype(np.float32)
    plan = make_plan(n, N, K=K, eps=eps, delta=0.1, value_range=8.0,
                     tile=tile, block=block)
    Vp, qp = _pad_operands(jnp.asarray(V), jnp.asarray(q), plan)
    V4 = _tile_major(Vp, plan)
    qb = qp.reshape(plan.n_blocks, plan.block)
    perm = jax.random.permutation(jax.random.PRNGKey(seed), plan.n_blocks)
    flat = flatten_schedule(plan.schedule, final_coverage=final_exact)
    cols = np.asarray(perm)[flat.col_pos]
    return V, q, plan, V4, qb, flat, cols


class TestFusedCascade:
    """The single-dispatch cascade kernel vs the step-accurate oracle."""

    @pytest.mark.parametrize("n,N,K,tile,block", [
        (512, 2048, 3, 8, 128),      # aligned
        (517, 2100, 3, 8, 256),      # ragged: n % tile != 0, N % block != 0
        (123, 300, 12, 8, 64),       # K > tile with ragged everything
        (64, 4096, 2, 4, 512),       # tall blocks, few tiles
    ])
    @pytest.mark.parametrize("final_exact", [False, True])
    def test_parity_vs_oracle(self, n, N, K, tile, block, final_exact):
        from repro.kernels.fused_cascade import fused_cascade_pallas

        _, _, plan, V4, qb, flat, cols = _fused_setup(
            n, N, K, tile, block, final_exact=final_exact)
        slotcode, rmeta = flat.packed()
        ids_k, vals_k = fused_cascade_pallas(
            V4, qb, jnp.asarray(slotcode), jnp.asarray(rmeta),
            jnp.asarray(cols), n_arms=plan.n, K=plan.K,
            t_final=flat.t_final, n_final=flat.n_final,
            t_dense=flat.t_dense, interpret=True)
        ids_o, vals_o = ref.fused_cascade_ref(V4, qb, flat, cols,
                                              n_arms=plan.n, K=plan.K)
        np.testing.assert_array_equal(np.asarray(ids_k), ids_o)
        np.testing.assert_allclose(np.asarray(vals_k), vals_o,
                                   rtol=2e-5, atol=1e-6)

    def test_multiple_rounds_still_one_dispatch(self):
        """The acceptance check: dispatch count is 1 regardless of rounds."""
        from repro.core.boundedme_jax import _run_blocked, make_plan

        plan = make_plan(512, 2048, K=3, eps=0.3, delta=0.1, value_range=8.0,
                         tile=8, block=128)
        assert len(plan.schedule.rounds) >= 3  # a real multi-round cascade

        rng = np.random.default_rng(0)
        V = jnp.asarray(rng.normal(size=(512, 2048)), jnp.float32)
        q = jnp.asarray(rng.normal(size=2048), jnp.float32)

        def fused(V, q, k):
            return _run_blocked(V, q, k, plan=plan, use_pallas=True)

        jaxpr = jax.make_jaxpr(fused)(V, q, jax.random.PRNGKey(0))
        assert ops.count_pallas_calls(jaxpr.jaxpr) == 1

    def test_batched_kernel_matches_loop_of_singles(self):
        from repro.kernels.fused_cascade import (fused_cascade_batched_pallas,
                                                 fused_cascade_pallas)
        from repro.core.boundedme_jax import _pad_operands, _tile_major, \
            make_plan
        from repro.core.schedule import flatten_schedule

        rng = np.random.default_rng(3)
        n, N, B = 256, 1024, 3
        V = rng.normal(size=(n, N)).astype(np.float32)
        Q = rng.normal(size=(B, N)).astype(np.float32)
        plan = make_plan(n, N, K=2, eps=0.2, delta=0.1, value_range=8.0,
                         block=128)
        Vp, Qp = _pad_operands(jnp.asarray(V), jnp.asarray(Q), plan)
        V4 = _tile_major(Vp, plan)
        Qb = Qp.reshape(B, plan.n_blocks, plan.block)
        keys = jax.random.split(jax.random.PRNGKey(1), B)
        perms = jax.vmap(
            lambda k: jax.random.permutation(k, plan.n_blocks))(keys)
        flat = flatten_schedule(plan.schedule)
        slotcode, rmeta = flat.packed()
        cols = jnp.take(perms, jnp.asarray(flat.col_pos), axis=1)
        kw = dict(n_arms=plan.n, K=plan.K, t_final=flat.t_final,
                  n_final=flat.n_final, t_dense=flat.t_dense, interpret=True)
        ids_b, vals_b = fused_cascade_batched_pallas(
            V4, Qb, jnp.asarray(slotcode), jnp.asarray(rmeta), cols, **kw)
        for b in range(B):
            ids_s, vals_s = fused_cascade_pallas(
                V4, Qb[b], jnp.asarray(slotcode), jnp.asarray(rmeta),
                cols[b], **kw)
            np.testing.assert_array_equal(np.asarray(ids_b[b]),
                                          np.asarray(ids_s))
            np.testing.assert_array_equal(np.asarray(vals_b[b]),
                                          np.asarray(vals_s))

    def test_saturated_rounds_no_pull_steps(self):
        """Tiny n_blocks saturates t at N: rounds with t_new == 0 still
        eliminate (no-pull steps carry the round-end flag)."""
        from repro.kernels.fused_cascade import fused_cascade_pallas

        _, _, plan, V4, qb, flat, cols = _fused_setup(400, 256, 1, 8, 64,
                                                      eps=0.05, seed=5)
        assert any(r.t_new == 0 for r in plan.schedule.rounds)
        slotcode, rmeta = flat.packed()
        ids_k, vals_k = fused_cascade_pallas(
            V4, qb, jnp.asarray(slotcode), jnp.asarray(rmeta),
            jnp.asarray(cols), n_arms=plan.n, K=plan.K,
            t_final=flat.t_final, n_final=flat.n_final,
            t_dense=flat.t_dense, interpret=True)
        ids_o, vals_o = ref.fused_cascade_ref(V4, qb, flat, cols,
                                              n_arms=plan.n, K=plan.K)
        np.testing.assert_array_equal(np.asarray(ids_k), ids_o)


def test_ops_wrappers_dispatch_interpret_on_cpu():
    assert not ops.on_tpu()
    V4 = jnp.asarray(_v4(2, 2, 8, 128, np.float32))
    out = ops.gather_block_dot(V4, jnp.zeros((1,), jnp.int32),
                               jnp.zeros((1,), jnp.int32),
                               jnp.ones((1, 128)))
    assert out.shape == (1, 8)


def _grid_steps(fn, *args):
    """Grid size of the one `pallas_call` that ``fn(*args)`` traces."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for v in eqn.params.values():
                sub = getattr(v, "jaxpr", None)
                sub = getattr(sub, "jaxpr", sub)
                if sub is not None and (hit := find(sub)) is not None:
                    return hit
        return None

    grid = find(jax.make_jaxpr(fn)(*args).jaxpr).params["grid_mapping"].grid
    assert len(grid) == 1
    return grid[0]


def _dense_case(bound="hoeffding", precision="fp32", n=800, N=4096,
                block=512, B=3, eps=0.7, seed=0, pull_mode="row"):
    """A table whose 100 tiles (800 rows) make two dense tile groups of
    64 at fp32 and block 512, the second one short."""
    from repro.core.boundedme_jax import make_plan

    rng = np.random.default_rng(seed)
    V = rng.normal(size=(n, N)).astype(np.float32)
    Q = rng.normal(size=(B, N)).astype(np.float32)
    plan = make_plan(n, N, K=2, eps=eps, delta=0.1, value_range=8.0,
                     block=block, bound=bound, precision=precision,
                     pull_mode=pull_mode,
                     quant_err=0.05 if precision == "pq" else None)
    return V, Q, plan


def _assert_same(a, b, what):
    for x, y, name in zip(a, b, ("ids", "scores", "rounds_used")):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{name} {what}")


def dense_parity(layout, B, mode, precision="fp32", n=800, N=4096,
                 block=512, eps=0.7, pull_mode="row", lean=None):
    """Kernel (dense round 1) vs both jnp fallbacks, bit for bit.

    ``lean``: queries ``j`` of ``(j, row, weight)`` lean on one row, so
    they certify early in adaptive mode.  Returns the kernel's output."""
    from repro.core.boundedme_jax import bounded_me_batched, \
        bounded_me_decode

    adaptive = mode != "fixed"
    bound = "bernstein" if mode == "bernstein" else "hoeffding"
    V, Q, plan = _dense_case(bound, precision, n=n, N=N, block=block, B=B,
                             eps=eps, pull_mode=pull_mode)
    for j, row, w in lean or ():
        Q[j] += w * V[row]
    assert plan.schedule.rounds[0].t_cum < plan.n_blocks   # later pulls
    key = jax.random.PRNGKey(B)
    if layout == "shared":   # the decode path: one permutation, (S,) cols
        kw = dict(plan=plan, final_exact=False, k_out=plan.K,
                  adaptive=adaptive)
        for nv in (n - 23, n):                           # ragged, full
            out_k = bounded_me_decode(V, Q, key, use_pallas=True,
                                      n_valid=nv, **kw)
            out_j = bounded_me_decode(V, Q, key, use_pallas=False,
                                      n_valid=nv, **kw)
            _assert_same(out_k, out_j, f"vs decode fallback, n_valid {nv}")
        # the scan path with every query on the same key pulls the same
        # columns in the same order
        out_s = bounded_me_batched(V, Q, jnp.stack([key] * B), plan=plan,
                                   final_exact=False, use_pallas=False,
                                   adaptive=adaptive)
        _assert_same(out_k, out_s, "vs scan path")
    else:                    # a permutation per query, (B, S) cols
        keys = jax.random.split(key, B)
        kw = dict(plan=plan, final_exact=False, adaptive=adaptive)
        out_k = bounded_me_batched(V, Q, keys, use_pallas=True, **kw)
        out_s = bounded_me_batched(V, Q, keys, use_pallas=False, **kw)
        _assert_same(out_k, out_s, "vs scan path")
    return out_k


class TestDenseRoundOne:
    """Round 1 pulled once for the batch, in multi-tile steps."""

    @pytest.mark.parametrize("mode", ["fixed", "hoeffding", "bernstein"])
    @pytest.mark.parametrize("B", [1, 3, 8])
    @pytest.mark.parametrize("layout", ["shared", "per_query"])
    def test_dense_phase_bitwise_vs_fallbacks(self, layout, B, mode):
        dense_parity(layout, B, mode)

    @pytest.mark.parametrize("layout", ["shared", "per_query"])
    def test_grid_runs_round_one_once(self, layout):
        """G1 + B S_g steps: G1 is round 1's dense steps, 64 tiles (1 MiB)
        each, and S_g a query's own: round 1's end, then each later
        round's columns in groups of up to G = 16 survivor tiles (one step
        for a round that pulls nothing)."""
        from repro.core.boundedme_jax import (_run_batched_fused,
                                              _run_decode,
                                              fused_flat_schedule)
        from repro.kernels.fused_cascade import pull_group_size

        B = 3
        V, Q, plan = _dense_case(B=B)
        rounds = plan.schedule.rounds
        t1 = rounds[0].t_cum
        assert (plan.n_tiles, t1) == (100, 7)
        G = pull_group_size(plan.tile, plan.block, jnp.float32)
        assert G == 16
        S_g = 1 + sum(r.t_new * -(-r.n_arms // G) if r.t_new else 1
                      for r in rounds[1:])
        assert S_g == len(fused_flat_schedule(
            plan, final_exact=False).groups(G)[0])
        assert S_g < sum(max(1, r.n_arms * r.t_new) for r in rounds[1:])
        groups = 1 if layout == "shared" else B
        G1 = groups * 2 * t1          # ceil(100 / 64) tile groups
        key = jax.random.PRNGKey(0)
        if layout == "shared":
            steps = _grid_steps(
                lambda V, Q, k: _run_decode(V, Q, k, plan.n, plan=plan,
                                            final_exact=False,
                                            use_pallas=True, k_out=plan.K),
                V, Q, key)
        else:
            steps = _grid_steps(
                lambda V, Q, k: _run_batched_fused(V, Q, k, plan=plan,
                                                   final_exact=False),
                V, Q, jax.random.split(key, B))
        assert steps == G1 + B * S_g

    def test_no_rounds_has_no_dense_phase(self):
        """K >= n_tiles: nothing to eliminate, sums start at zero."""
        from repro.core.boundedme_jax import (_run_decode,
                                              bounded_me_decode, make_plan)
        from repro.core.schedule import flatten_schedule

        rng = np.random.default_rng(1)
        V = rng.normal(size=(16, 256)).astype(np.float32)
        Q = rng.normal(size=(3, 256)).astype(np.float32)
        plan = make_plan(16, 256, K=8, eps=0.5, delta=0.1, value_range=8.0,
                         block=64)
        assert not plan.schedule.rounds
        assert flatten_schedule(plan.schedule).t_dense == 0
        key = jax.random.PRNGKey(2)
        assert _grid_steps(
            lambda V, Q, k: _run_decode(V, Q, k, 16, plan=plan,
                                        final_exact=False, use_pallas=True,
                                        k_out=plan.K),
            V, Q, key) == 3                  # one finalize step a query
        kw = dict(plan=plan, final_exact=False, n_valid=13)
        _assert_same(bounded_me_decode(V, Q, key, use_pallas=True, **kw),
                     bounded_me_decode(V, Q, key, use_pallas=False, **kw),
                     "no rounds")

    def test_pull_shares_of_command_r_shard_plan(self):
        """The counters' share, from the plan alone: Command-R's 64,000 x
        8,192 shard pulls 16,000 of its 29,220 tile-blocks a query in round
        1 (the flat schedule's 29,224 steps include four no-pull round
        ends)."""
        from repro.core.boundedme_jax import fused_flat_schedule, make_plan

        plan = make_plan(64000, 8192, K=1, eps=0.1, delta=0.1,
                         value_range=0.24, tile=8, block=512)
        flat = fused_flat_schedule(plan, final_exact=True)
        assert (flat.dense_pulls, flat.pull_steps) == (16_000, 29_220)
        assert flat.n_steps == 29_224 - 16_000 + 1


#: 2,000 rows (250 tiles) x 8,192 at block 512, K = 2: rounds 2+ pull 126,
#: 64, 33, 17, 9 and 5 survivors (fp32), so groups of 16 end short and
#: some rounds have fewer survivors than a group holds
_GROUPED = dict(n=2000, N=8192, eps=2.0)


def _short_groups(plan, G):
    """Rounds 2+ that pull: one with a short last group, one under G."""
    pulling = [r.n_arms for r in plan.schedule.rounds[1:] if r.t_new]
    return (any(a % G for a in pulling if a > G)
            and any(a < G for a in pulling))


class TestGroupedPulls:
    """Rounds 2+ pulled in groups of same-column survivor tiles, one grid
    step a group, bit for bit as the jnp fallbacks pull them one by one."""

    @pytest.mark.parametrize("mode", ["fixed", "hoeffding", "bernstein"])
    @pytest.mark.parametrize("B", [1, 3, 8])
    @pytest.mark.parametrize("layout", ["shared", "per_query"])
    @pytest.mark.parametrize("precision", ["fp32", "int8"])
    def test_grouped_pulls_bitwise_vs_fallbacks(self, precision, layout, B,
                                                mode):
        from repro.kernels.fused_cascade import pull_group_size

        _, _, plan = _dense_case(
            "bernstein" if mode == "bernstein" else "hoeffding", precision,
            block=512, B=B, **_GROUPED)
        dtype = jnp.float32 if precision == "fp32" else jnp.int8
        assert _short_groups(plan, pull_group_size(8, 512, dtype))
        dense_parity(layout, B, mode, precision, **_GROUPED)

    @pytest.mark.parametrize("mode,w", [("hoeffding", 0.3),
                                        ("bernstein", 3.5)])
    def test_a_query_that_freezes_mid_schedule(self, mode, w):
        """Query 0 leans on two rows and certifies after a few grouped
        rounds; its batchmates pull on after it froze."""
        rounds_used = dense_parity("shared", 3, mode,
                                   lean=[(0, 77, w), (0, 1234, w)],
                                   **_GROUPED)[2]
        n_rounds = len(_dense_case(
            "bernstein" if mode == "bernstein" else "hoeffding",
            **_GROUPED)[2].schedule.rounds)
        r = np.asarray(rounds_used)
        assert 1 < r[0] < min(r[1:]) and max(r[1:]) <= n_rounds

    @pytest.mark.parametrize("layout", ["shared", "per_query"])
    def test_coord_mode_groups_bitwise(self, layout):
        """128-wide coordinate tiles: 64 a group, summed by `f32_tile_dot`
        on the slab."""
        from repro.kernels.fused_cascade import pull_group_size

        _, _, plan = _dense_case(pull_mode="coord", **_GROUPED)
        assert plan.block == 128
        G = pull_group_size(8, 128, jnp.float32)
        assert G == 64 and _short_groups(plan, G)
        dense_parity(layout, 3, "fixed", pull_mode="coord", **_GROUPED)

    def test_final_coverage_steps_group_too(self):
        """``final_exact``: the final survivors' coverage steps are groups
        of ``n_final`` tiles; every block of a returned row is summed in
        permutation order, as a plain scan sums it."""
        from repro.core.boundedme_jax import (bounded_me_decode,
                                              fused_flat_schedule)
        from repro.core.quantize import f32_tile_dot

        V, Q, plan = _dense_case(**dict(_GROUPED, eps=3.0))
        flat = fused_flat_schedule(plan, final_exact=True)
        assert flat.t_final == plan.n_blocks > plan.schedule.final_pulls
        key = jax.random.PRNGKey(5)
        kw = dict(plan=plan, final_exact=True, k_out=plan.K)
        ids, vals = bounded_me_decode(V, Q, key, use_pallas=True, **kw)
        ids_j, _ = bounded_me_decode(V, Q, key, use_pallas=False, **kw)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_j))
        C = plan.block
        V4 = jnp.asarray(V).reshape(plan.n_tiles, 8, plan.n_blocks, C)
        Qb = jnp.asarray(Q).reshape(3, plan.n_blocks, C)
        perm = jax.random.permutation(key, plan.n_blocks)

        def add(s, col):   # (B, n_tiles, R) sums, one block a step
            return s + f32_tile_dot(V4[None, :, :, col],
                                    Qb[:, None, col]), None
        sums = jax.lax.scan(add, jnp.zeros((3, plan.n_tiles, 8)), perm)[0]
        rows = np.asarray(ids)
        want = (sums.reshape(3, -1)[jnp.arange(3)[:, None], rows]
                / jnp.float32(plan.n_blocks * C))
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(want))


def test_cols_must_match_the_dense_layout():
    """Columns built from ``bpos`` alone miss round 1's dense columns: the
    kernel refuses them rather than drop round 1's pulls."""
    from repro.kernels.fused_cascade import fused_cascade_pallas

    _, _, plan, V4, qb, flat, cols = _fused_setup(512, 2048, 3, 8, 128)
    assert flat.t_dense > 0
    slotcode, rmeta = flat.packed()
    with pytest.raises(ValueError, match="col_pos"):
        fused_cascade_pallas(
            V4, qb, jnp.asarray(slotcode), jnp.asarray(rmeta),
            jnp.asarray(cols[flat.t_dense:]), n_arms=plan.n, K=plan.K,
            t_final=flat.t_final, n_final=flat.n_final,
            t_dense=flat.t_dense, interpret=True)


@pytest.mark.parametrize("T,R,C", [(16, 8, 512), (16, 8, 384), (16, 8, 256),
                                   (32, 4, 1024), (8, 16, 200), (3, 8, 512)])
def test_lane_dense_tile_dot_matches_f32_tile_dot_bitwise(T, R, C):
    """The dense phase's transposed reduction (for blocks wider than 128)
    adds the same pairs as `f32_tile_dot`'s tree: the same bits."""
    from repro.core.quantize import f32_tile_dot
    from repro.kernels.fused_cascade import _tile_dot_lanes

    rng = np.random.default_rng(T * C + R)
    tiles = rng.normal(size=(T, R, C)).astype(np.float32)
    qcol = rng.normal(size=(1, C)).astype(np.float32)
    want = np.asarray(jax.jit(f32_tile_dot)(tiles, qcol))
    got = np.asarray(jax.jit(_tile_dot_lanes)(tiles, qcol))
    assert got.shape == (1, T * R)
    np.testing.assert_array_equal(got.reshape(T, R), want)
