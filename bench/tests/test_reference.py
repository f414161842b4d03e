"""The reference scores a bf16 table's stored values with exact fp32 products."""

import numpy as np
import pytest

from bench import checks, loadgen, reference
from bench.tests.tiny import tiny_spec


def test_the_reference_over_a_bf16_table_is_float64_of_the_stored_values():
    spec = tiny_spec(table_dtype="bfloat16", K=3)
    cfg, mix = spec["config"], spec["mix"]
    seed, nv, K = 2 ** 33 + 11, cfg["n_valid"], cfg["K"]
    V = loadgen.make_table(cfg, seed)
    Q, _ = loadgen.make_queries(V, cfg, mix, seed, 300)
    s64 = (Q.astype(np.float64) @ np.asarray(V, np.float64)[:nv].T) / cfg["d"]
    want = np.argsort(-s64, axis=1)[:, :K]
    served = np.stack([want[:, 0], want[:, 2], want[:, 1]], axis=1)
    top_ids, top_sc, got = reference.exact_answers(V, nv, Q, served, K)
    np.testing.assert_array_equal(top_ids, want)
    best = np.take_along_axis(s64, want, axis=1)
    assert np.max(np.abs(top_sc - best) / np.abs(best)) < 1e-6
    exact = np.take_along_axis(s64, served, axis=1)
    assert np.max(np.abs(got - exact) / np.abs(exact)) < 1e-6


@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16"])
def test_calibrate_reads_the_control_below_how_the_table_is_stored(
        table_dtype):
    # fp32: three bf16 passes; bf16: one bf16 pass; each fails the check
    from bench import calibrate

    spec = tiny_spec(table_dtype=table_dtype)
    out = calibrate.reference_readings(spec, 2 ** 33 + 13, 256)
    assert set(out) == {"control", "control_xla_high", "fault_altered",
                        "fault_half"}
    limits = spec["config"]["limits"]
    assert not checks.judge(out["control"], limits)
    assert out["control"]["score_rel_err"] > limits["score_rel_err"]
