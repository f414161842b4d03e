"""The check catches the control and each fault a cell can have.

Each test drives a whole harness run at a tiny size on the CPU (the look
for a chip skipped) with the timed path broken underneath, and sees
``correct`` come out false; the sound run beside them comes out true.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench.tests.tiny import run_tiny, tiny_spec

ROOT = Path(__file__).resolve().parents[2]


def _break_dispatch(monkeypatch, answer):
    """Replace what the executor's dispatch returns by ``answer(ex, Q)``."""
    from repro.launch import engine

    real = engine.CascadeExecutor.dispatch

    def dispatch(self, Qbuf, key):
        ids, scores, rounds, dt = real(self, Qbuf, key)
        ids, scores = answer(self, np.asarray(Qbuf), ids, scores)
        return ids, scores, rounds, dt

    monkeypatch.setattr(engine.CascadeExecutor, "dispatch", dispatch)


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_a_sound_run_is_correct(loop):
    out = run_tiny(tiny_spec(loop=loop))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


def test_the_control_in_the_programs_place_is_not_correct(monkeypatch):
    from bench import reference

    def control(ex, Q, ids, scores):
        c_ids, c_sc = reference.control_answers(
            ex.table, ex._nv_static, Q, ids.shape[1])
        return c_ids, c_sc

    _break_dispatch(monkeypatch, control)
    out = run_tiny(tiny_spec())
    assert not out["correct"]
    assert out["checks"]["score_rel_err"]["value"] > \
        out["checks"]["score_rel_err"]["limit"]


@pytest.mark.parametrize("answer", ["exact", "bf16_pass"])
def test_a_bf16_table_is_checked_against_its_stored_values(monkeypatch,
                                                          answer):
    # the exact fp32 top-K of the stored bf16 rows in the program's place
    # is correct; one bf16 pass, the step below, is not
    import ml_dtypes

    from bench import reference

    seen = []

    def replace(ex, Q, ids, scores):
        seen.append(ex.table.dtype)
        K = ids.shape[1]
        if answer == "exact":
            top, sc, _ = reference.exact_answers(
                ex.table, ex._nv_static, Q, np.zeros((len(Q), K), np.int32),
                K)
            return top, sc
        return reference.control_answers(
            ex.table, ex._nv_static, Q, K,
            precision=reference.CONTROL["bfloat16"])

    _break_dispatch(monkeypatch, replace)
    out = run_tiny(tiny_spec(table_dtype="bfloat16"))
    assert seen and all(d == ml_dtypes.bfloat16 for d in seen)
    err = out["checks"]["score_rel_err"]
    if answer == "exact":
        assert out["correct"], out["checks"]
    else:
        assert not out["correct"]
        assert err["value"] > err["limit"]


@pytest.mark.parametrize("part", ["ids", "scores"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        monkeypatch, part):
    def alter(ex, Q, ids, scores):
        if part == "ids":
            return (ids + 1) % int(ex._nv_static), scores
        return ids, scores * np.float32(1.001)

    _break_dispatch(monkeypatch, alter)
    out = run_tiny(tiny_spec(K=2))
    assert not out["correct"]


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    # lanes past the first half get the first half's answers
    def halve(ex, Q, ids, scores):
        ids, scores = np.array(ids), np.array(scores)
        h = ids.shape[0] // 2
        ids[h:2 * h], scores[h:2 * h] = ids[:h], scores[:h]
        return ids, scores

    _break_dispatch(monkeypatch, halve)
    out = run_tiny(tiny_spec())
    assert not out["correct"]
    assert out["checks"]["top1_miss_share"]["value"] > 0.3


_FOUR = """
import json, sys
sys.path[:0] = {paths!r}
import jax, jax.numpy as jnp
from bench.tests.tiny import run_tiny, tiny_spec
if {broken!r}:
    def local_only(x, axis_name, *, axis=0, tiled=False):
        return jnp.stack([x] * 4, axis=axis)
    jax.lax.all_gather = local_only
print(json.dumps(run_tiny(tiny_spec(chips=4))))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_the_exchange_between_chips_left_out_is_not_correct(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _FOUR.format(paths=[str(ROOT), str(ROOT / "src")],
                        broken=broken)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is (not broken), out["checks"]
    if broken:
        assert out["checks"]["top1_miss_share"]["value"] > 0.5
