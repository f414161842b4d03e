"""Sweep of an open-loop cell's arrival rate, to find the rate it sustains.

    python3 bench/sweep.py --workload <open-loop cell> --rates 12 16 20 \
        --seeds 1 2 3 --seconds 20

One process; for each rate and seed, one run of the cell as the
benchmark makes it with the mix's ``rate_qps`` replaced, printing the
answered rate, the latency percentiles and how late the generator ran.
The highest rate whose answered rate keeps up with it, and whose tail
does not grow with the window, is the cell's knee; the cell's traffic
file then states about four fifths of it.  Not run by the benchmark.
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

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    spec = harness.load_spec(args.workload)
    if spec["mix"]["loop"] != "open":
        ap.error(f"{args.workload} is not an open-loop cell")
    # the answered rate shows whether the system keeps up with the offer
    spec["end_to_end"].append({"name": "qps", "unit": "queries/s"})
    for rate in args.rates:
        for seed in args.seeds:
            spec["mix"]["rate_qps"] = rate
            out = harness.run(spec, seed, args.seconds, False,
                              t_start=time.perf_counter())
            m = {k: v["value"] for k, v in out["metrics"].items()}
            print(json.dumps({"rate_qps": rate, "seed": seed,
                              "correct": out["correct"],
                              "attempted": out["attempted"],
                              "failed": out["failed"], **m,
                              "generator_late_ms_max":
                                  out.get("generator_late_ms_max")}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
