"""A cell small enough for a CPU test run of the whole harness."""

import json
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent.parent


def tiny_spec(chips: int = 1, loop: str = "closed", K: int = 1,
              table_dtype: Optional[str] = None) -> dict:
    """The cmdr35b configuration cut to 1,000 x 1,536, with its limits.

    ``table_dtype``, when given, is set in the configuration (which
    states none: fp32).
    """
    cfg = json.loads((BENCH / "configs" / "cmdr35b-lmhead-shard4.json")
                     .read_text())
    cfg.update(rows=1024, n_valid=1000, d=1536, K=K)
    if table_dtype is not None:
        cfg["table_dtype"] = table_dtype
    name = "decode8" if loop == "closed" else "poisson"
    mix = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    mix.update(pool=256)
    if loop == "open":
        mix.update(rate_qps=200.0)
    lat = "latency" if loop == "closed" else "open_latency"
    metric = [{"name": n, "unit": "x"} for n in
              ("qps", f"{lat}_p50_ms", f"{lat}_p95_ms", "setup_s")]
    return {"cell": {"name": "tiny", "chips": chips}, "config": cfg,
            "mix": mix, "end_to_end": metric,
            "per_layer": [{"name": "dispatch_ms", "unit": "ms"},
                          {"name": "batch_occupancy", "unit": "requests"},
                          {"name": "queue_wait_p95_ms", "unit": "ms"}]}


def run_tiny(spec: dict, seed: int = 2 ** 33 + 7, seconds: float = 0.5,
             trace: bool = False) -> dict:
    """One harness run of ``spec`` on whatever devices JAX has."""
    import time

    from bench import harness

    return harness.run(spec, seed, seconds, trace,
                       t_start=time.perf_counter(), require_tpu=False)
