"""FlatSchedule invariants: the flattening consumed by the fused kernel."""

import numpy as np
import pytest

from repro.core.schedule import (END_BIT, PULL_BIT, SLOT_MASK,
                                 flatten_schedule, make_schedule)


def _flat(n=50, N=40, K=2, eps=0.2, delta=0.1, **kw):
    return make_schedule(n, N, K=K, eps=eps, delta=delta), kw


@pytest.mark.parametrize("n,N,K,eps", [(50, 40, 2, 0.2), (400, 4, 1, 0.05),
                                       (7, 100, 3, 0.4), (64, 64, 8, 0.1)])
def test_flatten_invariants(n, N, K, eps):
    sched = make_schedule(n, N, K=K, eps=eps, delta=0.1)
    flat = flatten_schedule(sched)
    # one end flag per round, in order
    assert int(flat.is_end.sum()) == len(sched.rounds)
    # pull steps count = total sample complexity of the schedule, round 1
    # served by the dense phase: every arm, its first t_cum blocks
    assert flat.pull_steps == sched.total_pulls
    first = sched.rounds[0] if sched.rounds else None
    assert flat.t_dense == (first.t_cum if first else 0)
    assert flat.n_dense == (first.n_arms if first else 0)
    assert flat.dense_pulls == (first.n_arms * first.t_new if first else 0)
    # the per-query part opens with round 1's no-pull end step
    if first:
        assert (flat.is_pull[0], flat.is_end[0]) == (0, 1)
    # the kernel reads the dense columns in order, then one per step
    np.testing.assert_array_equal(
        flat.col_pos, np.r_[np.arange(flat.t_dense), flat.bpos])
    # slots stay inside the round's survivor count
    assert (flat.slot < flat.n_surv).all()
    # block positions stay inside the reward list
    assert (flat.bpos >= 0).all() and (flat.bpos < N).all()
    # survivor counts per round follow the elimination chain
    ends = np.nonzero(flat.is_end)[0]
    for j, r in zip(ends, sched.rounds):
        assert flat.n_surv[j] == r.n_arms
        assert flat.n_keep[j] == r.n_keep
        assert flat.t_cum[j] == r.t_cum
    assert flat.n_final == (sched.rounds[-1].n_keep if sched.rounds
                            else sched.n)
    assert flat.t_final == (sched.rounds[-1].t_cum if sched.rounds else 0)


def test_flatten_saturated_round_emits_noop_end_step():
    sched = make_schedule(400, 4, K=1, eps=0.05, delta=0.1)
    assert any(r.t_new == 0 for r in sched.rounds)
    flat = flatten_schedule(sched)
    noop_ends = (flat.is_pull == 0) & (flat.is_end == 1)
    # round 1's end step pulls nothing either: the dense phase pulled it
    assert noop_ends.sum() == 1 + sum(r.t_new == 0
                                      for r in sched.rounds[1:])


def test_flatten_final_coverage_completes_to_N():
    sched = make_schedule(64, 32, K=2, eps=0.3, delta=0.1)
    flat = flatten_schedule(sched, final_coverage=True)
    assert flat.t_final == sched.N
    # coverage pulls touch every survivor for every remaining block
    extra = flat.n_steps - flatten_schedule(sched).n_steps
    t_last = sched.rounds[-1].t_cum
    assert extra == (sched.N - t_last) * flat.n_final


def test_flatten_degenerate_no_rounds():
    sched = make_schedule(8, 16, K=8)          # K >= n: nothing to eliminate
    assert not sched.rounds
    flat = flatten_schedule(sched)
    assert flat.n_steps == 1                   # single no-op finalize step
    assert int(flat.is_pull.sum()) == 0 and int(flat.is_end.sum()) == 0


def test_packed_roundtrip():
    sched = make_schedule(50, 40, K=2, eps=0.2, delta=0.1)
    flat = flatten_schedule(sched, final_coverage=True)
    code, meta = flat.packed()
    assert code.dtype == np.int32 and meta.dtype == np.int32
    np.testing.assert_array_equal(code & SLOT_MASK, flat.slot)
    np.testing.assert_array_equal((code & PULL_BIT) != 0, flat.is_pull == 1)
    np.testing.assert_array_equal((code & END_BIT) != 0, flat.is_end == 1)
    assert meta.shape == (len(sched.rounds) + 1, 3)
    for j, r in enumerate(sched.rounds):
        assert tuple(meta[j]) == (r.t_cum, r.n_arms, r.n_keep)


@pytest.mark.parametrize("G", [1, 4, 16, 64])
@pytest.mark.parametrize("n,N,K,eps,cover", [
    (50, 40, 2, 0.2, False), (400, 4, 1, 0.05, False),
    (250, 64, 1, 0.3, True), (7, 100, 3, 0.4, True), (8, 16, 8, 0.1, True)])
def test_groups_stay_in_one_column_of_one_round(n, N, K, eps, cover, G):
    """A grid step pulls consecutive slots of one column, opens on a
    multiple of G, ends before a column change or a round end, and the
    group sizes sum to the per-query pulls."""
    from repro.core.schedule import group_steps

    flat = flatten_schedule(make_schedule(n, N, K=K, eps=eps, delta=0.1),
                            final_coverage=cover)
    first, size = flat.groups(G)
    assert first[0] == 0 and (np.diff(first) > 0).all()
    assert int(size.sum()) == int(flat.is_pull.sum())
    assert size.max(initial=0) <= G
    stop = np.r_[first[1:], flat.n_steps]
    for f, z, e in zip(first, size, stop):
        if z == 0:                         # a no-pull step stays alone
            assert e == f + 1 and not flat.is_pull[f]
            continue
        run = slice(f, e)
        assert e - f == z and flat.is_pull[run].all()
        assert flat.slot[f] % G == 0
        np.testing.assert_array_equal(flat.slot[run],
                                      flat.slot[f] + np.arange(z))
        assert (flat.bpos[run] == flat.bpos[f]).all()       # one column
        assert (flat.t_cum[run] == flat.t_cum[f]).all()     # one round
        assert not flat.is_end[f:e - 1].any()   # an end closes a group
        assert z == min(G, flat.n_surv[f] - flat.slot[f])
    # the grouped words: each group's first, with its last step's END_BIT
    code, meta = flat.packed()
    words, at = group_steps(code, G)
    np.testing.assert_array_equal(at, first)
    np.testing.assert_array_equal(words & SLOT_MASK, flat.slot[first])
    np.testing.assert_array_equal((words & PULL_BIT) != 0, size > 0)
    np.testing.assert_array_equal((words & END_BIT) != 0,
                                  flat.is_end[stop - 1] == 1)
    if G == 1:                             # one tile a step: unchanged
        np.testing.assert_array_equal(words, code)


@pytest.mark.parametrize("rows,d,K,groups,pulls", [
    (64_000, 8_192, 1, 831, 29_220),     # the Command-R shard
    (53_248, 1_024, 5, 0, 13_312)])      # Whisper: round 1 pulls it all
def test_cell_plans_group_their_later_pulls(rows, d, K, groups, pulls):
    """At G = 16 the Command-R shard's 13,220 pulls after round 1 take 831
    grid steps (250 + 250 + 189 + 96 + 32 + 8 + 4 + 2) and Whisper's
    none; the pulls themselves are unchanged."""
    from repro.core.boundedme_jax import fused_flat_schedule, make_plan

    plan = make_plan(rows, d, K=K, eps=0.1, delta=0.1, value_range=0.24,
                     tile=8, block=min(512, d))
    flat = fused_flat_schedule(plan, final_exact=True)
    first, size = flat.groups(16)
    assert int((size > 0).sum()) == groups
    assert flat.pull_steps == pulls
    if groups:
        assert flat.dense_pulls == 16_000
        per_round = [int(size[(size > 0) & (flat.t_cum[first] == r.t_cum)
                              ].size) for r in plan.schedule.rounds[1:]
                     if r.t_new]
        assert per_round == [250, 250, 189, 96, 32, 8, 4, 2]
    else:
        assert len(first) == flat.n_steps == 13   # the grid is unchanged
