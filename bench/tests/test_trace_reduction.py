"""Reduction of a trace to kernel, driver, collective and idle numbers."""

import pytest

from bench import xplane


def _op(name, t0, t1, module="jit__flush_fn", opcode="fusion"):
    return {"name": name, "t0": t0, "t1": t1, "module": module,
            "text": f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)"}


def _span(name, t0, t1):
    return {"name": f"bench.{name}", "t0": t0, "t1": t1}


def _records():
    # window [100, 1100] ns on two devices
    dev0 = [_op("fusion.1", 50, 150),                  # half inside
            _op("fused_cascade.3", 200, 600, opcode="custom-call"),
            _op("all-gather.1", 600, 650, opcode="all-gather"),
            _op("copy.2", 700, 800, module="jit_fold_in"),
            _op("fusion.9", 2000, 2100)]               # outside
    dev1 = [_op("fused_cascade.3", 200, 500, opcode="custom-call"),
            _op("fusion.1", 500, 600)]
    host = [_span("window", 100, 1100), _span("submit", 100, 190),
            _span("poll", 190, 1000), _span("result", 1000, 1100)]
    return {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
            "host": host}


def test_union_merges_overlaps():
    assert xplane.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4),
                                                              (5, 8)]


def test_reduction_clips_to_the_window_and_averages_devices():
    r = xplane.reduce_trace(_records())
    assert r["window_s"] == pytest.approx(1000e-9)
    # dev0 busy: 100..150, 200..650, 700..800 = 50+450+100; dev1: 400
    assert r["busy_s"] == pytest.approx((600 + 400) / 2 * 1e-9)
    assert r["kernel_s"] == pytest.approx((400 + 300) / 2 * 1e-9)
    assert r["kernel_ops"] == 2
    assert r["collective_s"] == pytest.approx(50 / 2 * 1e-9)
    # flush ops outside kernel and collectives: 50 (dev0) + 100 (dev1)
    assert r["driver_s"] == pytest.approx(150 / 2 * 1e-9)
    assert r["devices"] == 2


def test_idle_gaps_are_named_by_the_host_phase():
    r = xplane.reduce_trace(_records())
    gaps = dict((name, s) for name, s in r["idle_gaps"])
    # dev0 holes: 150..200 (submit 40 ns, poll 10 ns), 650..700 (poll),
    # 800..1100 (poll 200 ns, result 100 ns)
    assert r["idle_gaps"][0][0] == "poll"
    assert r["idle_gaps"][0][1] == pytest.approx(300e-9)
    assert set(gaps) == {"poll", "submit"}
    assert len(r["idle_gaps"]) == 3


def test_top_device_ops_by_time():
    r = xplane.reduce_trace(_records())
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "fused_cascade.3"
    assert "fusion.9" not in names


def test_no_window_or_no_device_gives_nothing():
    rec = _records()
    assert xplane.reduce_trace({"devices": rec["devices"],
                                "host": rec["host"][1:]}) is None
    assert xplane.reduce_trace({"devices": {}, "host": rec["host"]}) is None


@pytest.mark.parametrize("op,kernel,coll", [
    (_op("pallas.1", 0, 1, opcode="custom-call"), True, False),
    (_op("ag.1", 0, 1, opcode="all-gather-start"), False, True),
    (_op("all-reduce.4", 0, 1, opcode="all-reduce"), False, True),
    (_op("all_gather_fusion", 0, 1), False, False),
    (_op("fusion.3", 0, 1), False, False)])
def test_op_classes(op, kernel, coll):
    assert xplane.is_kernel(op) == kernel
    assert xplane.is_collective(op) == coll


def test_a_recorded_trace():
    """5 s of cmdr35b-shard4.decode8 on one TPU v5 lite, 18 dispatches."""
    from pathlib import Path

    path = Path(__file__).parent / "data" / "cmdr35b-shard4.decode8.xplane.pb"
    r = xplane.reduce_trace(xplane.load(str(path)))
    assert r["devices"] == 1 and r["kernel_ops"] == 18
    assert r["device_ops"][0][0] == "fused_cascade_batched_pallas.1"
    assert 0.9 < r["kernel_s"] / r["window_s"] < r["busy_s"] / r["window_s"]
    assert r["busy_s"] <= r["window_s"]
    assert 0 < r["driver_s"] < 0.01 * r["kernel_s"]
    assert r["collective_s"] == 0.0
    assert {name for name, _ in r["idle_gaps"]} <= {"poll", "submit",
                                                     "result", "none"}
    assert r["idle_gaps"][0][1] >= r["idle_gaps"][-1][1] > 0
