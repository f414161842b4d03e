"""Readings that the limits of `bench.checks` are set from, on the chip.

    python3 bench/calibrate.py --workload <name> [--seeds 1 2 3 ...] \
        [--control-seeds 1 2 3] --seconds <s> [--out file.jsonl]

In one process, for each seed: one run of the cell as the benchmark makes
it (`bench.harness.run`), whose compared numbers are the program's
readings.  For each control seed, the same queries answered instead by

* ``control``: the reference one precision step below how the
  configuration stores its table (``table_dtype``, `bench.reference.CONTROL`):
  over fp32, fp32 at ``Precision.HIGH`` as three explicit bf16 passes;
  over bf16, one bf16 pass with the queries rounded to bf16
  (`bench.reference.control_answers`);
* ``control_xla_high``: XLA's own ``Precision.HIGH``, over fp32 the same
  step as ``control``;
* ``fault_shard``: the exact reference over the first shard's rows only,
  "the exchange between chips left out" (cells on several chips);
* ``fault_altered``: the exact answers with each returned row moved to
  the next row, "an answer altered where it is produced";
* ``fault_half``: the exact answers with the second half of each batch
  of ``lanes`` given the first half's, "half of the batch left out".

and the exact-scan time of one batch of ``lanes`` queries on the chip
(fp32 ``Q @ V.T`` at ``HIGHEST`` and ``top_k``, the median of 50).  Not
run by the benchmark's runs.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

from bench import checks, harness, loadgen, reference  # noqa: E402


def reference_readings(spec: dict, seed: int, count: int,
                       sharding=None) -> dict:
    """Compared numbers of the control and the faults on ``count`` queries."""
    cfg, mix = spec["config"], spec["mix"]
    chips = spec["cell"]["chips"]
    table = loadgen.make_table(cfg, seed, sharding)
    Q, _ = loadgen.make_queries(table, cfg, mix, seed, count)
    K, nv = cfg["K"], cfg["n_valid"]
    kw = dict(n_valid=nv, eps=cfg["eps"],
              value_range=loadgen.value_range(cfg), unanswered=0)
    ex_ids, ex_sc, _ = reference.exact_answers(
        table, nv, Q, np.zeros((count, K), np.int32), K)
    out = {}
    step = reference.CONTROL[loadgen.table_dtype(cfg)]
    answers = {"control": reference.control_answers(table, nv, Q, K,
                                                    precision=step),
               "control_xla_high": reference.control_answers(
                   table, nv, Q, K, precision="xla_high")}
    if chips > 1:
        answers["fault_shard"] = reference.control_answers(
            table, nv, Q, K, precision="highest",
            rows=(0, cfg["rows"] // chips))
    answers["fault_altered"] = ((ex_ids + 1) % nv, ex_sc)
    h = cfg["lanes"] // 2
    half = np.arange(count)
    half = np.where(half % cfg["lanes"] < h, half, half - h)
    answers["fault_half"] = (ex_ids[half], ex_sc[half])
    for name, (ids, sc) in answers.items():
        _, _, got = reference.exact_answers(table, nv, Q, ids, K)
        out[name] = checks.compare(ids, sc, ex_ids, ex_sc, got, **kw)
    return out


def exact_scan_ms(spec: dict, seed: int, sharding=None, reps: int = 50):
    """Median ms of one exact fp32 top-K of a ``lanes`` batch on the chip."""
    import jax
    import jax.numpy as jnp

    cfg, mix = spec["config"], spec["mix"]
    table = loadgen.make_table(cfg, seed, sharding)
    Q, _ = loadgen.make_queries(table, cfg, mix, seed, cfg["lanes"])
    nv, K = cfg["n_valid"], cfg["K"]
    out = {}
    for prec in ("highest", "default"):
        p = {"highest": jax.lax.Precision.HIGHEST,
             "default": jax.lax.Precision.DEFAULT}[prec]
        fn = jax.jit(lambda V, q: jax.lax.top_k(
            jnp.dot(q, V[:nv].T, precision=p,
                    preferred_element_type=jnp.float32), K))
        q = jnp.asarray(Q)
        jax.block_until_ready(fn(table, q))
        times = []
        for _ in range(reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(table, q))
            times.append((time.perf_counter() - t) * 1e3)
        out[prec] = float(np.median(times))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    spec = harness.load_spec(args.workload)
    chips = spec["cell"]["chips"]
    devs = harness.devices_for(chips, require_tpu=True)
    sharding = None
    if chips > 1:
        from repro.launch.mesh import make_serving_mesh
        sharding = NamedSharding(make_serving_mesh(chips), P("model", None))
    sink = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    attempted = []
    for seed in args.seeds:
        t = time.perf_counter()
        out = harness.run(spec, seed, args.seconds, False,
                          t_start=time.perf_counter())
        attempted.append(out["attempted"])
        emit({"workload": args.workload, "seed": seed, "side": "program",
              "correct": out["correct"], "attempted": out["attempted"],
              "metrics": {k: v["value"] for k, v in out["metrics"].items()},
              "numbers": {k: v["value"] for k, v in out["checks"].items()},
              "wall_s": time.perf_counter() - t,
              "device": out["device"]})
    count = int(np.median(attempted)) if attempted else 512
    for seed in args.control_seeds:
        for side, nums in reference_readings(spec, seed, count,
                                             sharding).items():
            emit({"workload": args.workload, "seed": seed, "side": side,
                  "queries": count, "numbers": nums})
    if args.control_seeds:
        emit({"workload": args.workload, "side": "exact_scan",
              "device": devs[0].device_kind,
              "batch": spec["config"]["lanes"],
              "ms": exact_scan_ms(spec, args.control_seeds[0], sharding)})
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
