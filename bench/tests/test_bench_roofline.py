"""The least-bytes count of a dispatch and the peaks table."""

import pytest

from bench import roofline


def _plan(n, d, K, vr, precision="fp32"):
    from repro.core.boundedme_jax import make_plan

    return make_plan(n, d, K=K, eps=0.1, delta=0.1, value_range=vr,
                     tile=8, block=512, precision=precision)


@pytest.mark.parametrize("n,d,K,vr", [(64000, 8192, 1, 0.24),
                                      (53248, 1024, 5, 0.24),
                                      (4096, 8192, 3, 1.0),
                                      (1000, 1536, 1, 0.24)])
def test_count_is_the_plan_pulls_times_tile_bytes(n, d, K, vr):
    plan = _plan(n, d, K, vr)
    rounds = plan.schedule.rounds
    last = rounds[-1]
    coverage = last.n_keep * (plan.n_blocks - last.t_cum)
    tile_bytes = plan.tile * plan.block * 4
    assert roofline.least_bytes(plan, final_coverage=False) == \
        plan.schedule.total_pulls * tile_bytes
    assert roofline.least_bytes(plan, final_coverage=True) == \
        (plan.schedule.total_pulls + coverage) * tile_bytes
    assert roofline.least_bytes(plan, final_coverage=True) <= \
        roofline.table_scan_bytes(plan)


def test_count_of_the_cmdr_shard():
    # 22.8% of 8,000 tiles x 16 blocks; the last round reads every block,
    # so final coverage adds nothing
    plan = _plan(64000, 8192, 1, 0.24)
    steps = roofline.pull_steps(plan.schedule.rounds, plan.n_blocks, True)
    assert steps == 29220
    assert roofline.least_bytes(plan, final_coverage=True) == 29220 * 16384


@pytest.mark.parametrize("precision,itemsize,per_elem", [
    ("fp32", 4, 4), ("int8", 4, 1), ("int4", 4, 0.5),
    ("fp32", 2, 2),                 # a bf16 table: half the fp32 bytes
    ("int8", 2, 1)])                # a quantized tier keeps its width
def test_narrow_tiers_are_priced_at_their_width(precision, itemsize,
                                                per_elem):
    assert roofline.slab_bytes(8, 512, precision,
                               table_itemsize=itemsize) == 8 * 512 * per_elem
    assert roofline.slab_bytes(8, 512, "pq", pq_subdims=8) == 8 * 64
    plan = _plan(64000, 8192, 1, 0.24, precision)
    steps = roofline.pull_steps(plan.schedule.rounds, plan.n_blocks, True)
    assert roofline.least_bytes(plan, final_coverage=True,
                                table_itemsize=itemsize) == \
        steps * 8 * 512 * per_elem
    assert roofline.table_scan_bytes(plan, itemsize) == \
        plan.n_tiles * plan.n_blocks * 8 * 512 * per_elem


def test_the_roofline_reader_prices_the_table_as_stored():
    from bench import harness

    read = harness._reader("fused_cascade_roofline")
    plan = _plan(64000, 8192, 1, 0.24)
    ctx = {"trace": {"kernel_ops": 10, "kernel_s": 1.5375},
           "window": {"dispatches": 10}, "plan": plan,
           "final_coverage": True, "device_kind": "TPU v5 lite"}
    fp32 = read(dict(ctx, table_itemsize=4))
    # 29,220 pulls of 16 KiB at 819 GB/s over 153.75 ms a dispatch
    assert fp32 == pytest.approx(100 * 29220 * 16384 / 819e9 / 0.15375)
    assert read(dict(ctx, table_itemsize=2)) == fp32 / 2


def test_unknown_device_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
