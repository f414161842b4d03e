"""DeepSeek-V3's head: its configuration file, and a whole harness run of a
DeepSeek-shaped tiny head on the CPU checked against the plain reference.

The tiny head keeps what the cell exercises: 14 coordinate blocks (d =
896 at block 64, as 7,168 is 14 blocks of 512), a ragged row count, a
bf16 table served at fp32, 16 lanes filled by a closed loop of 16
clients, and a plan that eliminates after round 1.
"""

import json
from pathlib import Path

import pytest

from bench import harness
from bench.tests.tiny import run_tiny

ROOT = Path(__file__).resolve().parents[2]
CELL = "dsv3-head.verify16"


def test_the_configuration_is_the_published_head():
    spec = harness.load_spec(CELL)
    cfg, mix = spec["config"], spec["mix"]
    assert cfg["published"] == {"vocab_size": 129280, "hidden_size": 7168,
                                "num_nextn_predict_layers": 1}
    assert (cfg["rows"], cfg["n_valid"], cfg["d"]) == (129280, 129280, 7168)
    assert (cfg["vocab_size"], cfg["hidden_size"]) == (129280, 7168)
    assert cfg["reduced"] == {}
    assert (cfg["table_dtype"], cfg["precision"]) == ("bfloat16", "fp32")
    assert (cfg["K"], cfg["lanes"]) == (1, 16)
    # MTP verifies the token and one draft for each of 8 sequences
    assert mix["clients"] == cfg["lanes"] == 8 * (
        1 + cfg["num_nextn_predict_layers"])
    assert spec["cell"]["chips"] == 1
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in manifest["configs"]}[cfg["name"]]
    assert entry["reduced"] == []
    # below what the controls read over this table (PERF.md s6)
    assert 0 < cfg["limits"]["score_rel_err"] < 1.47e-6
    reported = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
    assert reported == {"qps", "latency_p50_ms", "latency_p95_ms",
                        "setup_s", "kernel_ms", "fused_cascade_roofline",
                        "driver_ms", "dispatch_ms", "device_idle_share",
                        "batch_occupancy"}


def tiny_dsv3() -> dict:
    """The cell cut to 2,020 x 896 (14 blocks of 64), all else as run."""
    spec = harness.load_spec(CELL)
    spec["config"].update(rows=2020, n_valid=2020, d=896, block=64)
    spec["mix"].update(pool=256)
    spec["per_layer"] = [m for m in spec["per_layer"]
                         if m["name"] in ("dispatch_ms", "batch_occupancy")]
    return spec


def test_the_tiny_head_eliminates_after_round_one():
    from repro.core.boundedme_jax import fused_flat_schedule, make_plan

    from bench import loadgen

    cfg = tiny_dsv3()["config"]
    plan = make_plan(cfg["n_valid"], cfg["d"], K=cfg["K"], eps=cfg["eps"],
                     delta=cfg["delta"], value_range=loadgen.value_range(cfg),
                     tile=cfg["tile"], block=cfg["block"])
    flat = fused_flat_schedule(plan, final_exact=True)
    assert plan.n_blocks == 14
    assert 0 < flat.dense_pulls < flat.pull_steps < plan.n_tiles * 14


@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_a_tiny_dsv3_head_serves_16_lanes_correctly(trace):
    import ml_dtypes

    from bench import loadgen

    spec = tiny_dsv3()
    assert loadgen.make_table(spec["config"], 1).dtype == ml_dtypes.bfloat16
    out = run_tiny(spec, trace=trace)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 16
    assert out["checks"]["score_rel_err"]["limit"] < 1.47e-6
    if trace:
        occ = out["metrics"]["batch_occupancy"]["value"]
        assert occ == 16, occ
