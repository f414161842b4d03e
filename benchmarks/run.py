"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per benchmark, then the
pull-loop roofline table (``results/roofline.md``).  Also writes the
machine-readable perf trajectories: ``BENCH_PR1.json`` (fused cascade /
batched decode: us_per_call, pull-count speedup, kernel dispatch counts),
``BENCH_PR2.json`` (serve-loop micro-batching: throughput vs batch
deadline at B in {1, 8, 32}, LRU hit rates), ``BENCH_PR3.json``
(int8 quantized sampling vs fp32 at B in {1, 8, 32}),
``BENCH_PR4.json`` (dynamic-store serving under churn + update cost vs
LSH/PCA full rebuilds), ``BENCH_PR5.json`` (adaptive early-exit mean
pulls + rounds_used histograms, easy vs hard workloads) and
``BENCH_PR6.json`` (continuous-batching runtime: sustained rps / p99 /
shed rate under bursty load with and without injected faults, plus the
overload sweep showing the eps degradation ladder engaging) and
``BENCH_PR7.json`` (coordinate-sampling pull mode: certified multiplies
+ wall time per pull mode over the d sweep, hybrid dispatch overhead,
and the pull-loop roofline's bytes-per-pull cells) and
``BENCH_PR8.json`` (the fp32/int8/int4/pq precision ladder on a planted
compressible workload: bytes per pull, total sampling bytes, recall and
wall time per tier) and ``BENCH_PR10.json``
(multi-tenant serving: per-tenant answered fraction / shed / p99 under
hot-tenant skew, cold-tenant p99 vs a dedicated isolated baseline — gate:
ratio <= 2x with the hot tenant throttled not starving — and the
eviction/page-in cost of memory-budgeted table residency) so numbers
stay comparable across PRs.
"""

from __future__ import annotations

import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(__file__))
BENCH_JSON = os.path.join(_ROOT, "BENCH_PR1.json")
BENCH2_JSON = os.path.join(_ROOT, "BENCH_PR2.json")
BENCH3_JSON = os.path.join(_ROOT, "BENCH_PR3.json")
BENCH4_JSON = os.path.join(_ROOT, "BENCH_PR4.json")
BENCH5_JSON = os.path.join(_ROOT, "BENCH_PR5.json")
BENCH6_JSON = os.path.join(_ROOT, "BENCH_PR6.json")
BENCH7_JSON = os.path.join(_ROOT, "BENCH_PR7.json")
BENCH8_JSON = os.path.join(_ROOT, "BENCH_PR8.json")
BENCH10_JSON = os.path.join(_ROOT, "BENCH_PR10.json")


def main() -> None:
    from benchmarks import (bench_adaptive, bench_coord, bench_fused,
                            bench_quant, bench_runtime,
                            bench_serve, bench_store, bench_tenancy,
                            fig1_guarantee, fig23_synthetic, fig4_real,
                            roofline, table1_complexity)
    print("== fused cascade / batched decode (PR 1) ==")
    import jax
    meta = {"backend": jax.default_backend(),
            "devices": jax.device_count()}
    payload = {"meta": meta, "benchmarks": bench_fused.run()}
    with open(BENCH_JSON, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"[bench] wrote {BENCH_JSON}")
    print("== serve-loop micro-batching (PR 2) ==")
    payload2 = {"meta": meta, "benchmarks": bench_serve.run()}
    with open(BENCH2_JSON, "w") as f:
        json.dump(payload2, f, indent=2)
    print(f"[bench] wrote {BENCH2_JSON}")
    print("== int8 quantized sampling vs fp32 (PR 3) ==")
    payload3 = {"meta": meta, "benchmarks": bench_quant.run()}
    with open(BENCH3_JSON, "w") as f:
        json.dump(payload3, f, indent=2)
    print(f"[bench] wrote {BENCH3_JSON}")
    print("== dynamic table store: churn + update cost (PR 4) ==")
    payload4 = {"meta": meta, "benchmarks": bench_store.run()}
    with open(BENCH4_JSON, "w") as f:
        json.dump(payload4, f, indent=2)
    print(f"[bench] wrote {BENCH4_JSON}")
    print("== adaptive early-exit cascade (PR 5) ==")
    payload5 = {"meta": meta, "benchmarks": bench_adaptive.run()}
    with open(BENCH5_JSON, "w") as f:
        json.dump(payload5, f, indent=2)
    print(f"[bench] wrote {BENCH5_JSON}")
    print("== continuous-batching runtime under overload/faults (PR 6) ==")
    payload6 = {"meta": meta, "benchmarks": bench_runtime.run()}
    with open(BENCH6_JSON, "w") as f:
        json.dump(payload6, f, indent=2)
    print(f"[bench] wrote {BENCH6_JSON}")
    print("== coordinate pull mode + roofline (PR 7) ==")
    payload7 = {"meta": meta, "benchmarks": bench_coord.run(),
                "roofline": roofline.run()}
    with open(BENCH7_JSON, "w") as f:
        json.dump(payload7, f, indent=2)
    print(f"[bench] wrote {BENCH7_JSON}")
    print("== precision ladder: int4 + pq vs int8/fp32 (PR 8) ==")
    payload8 = {"meta": meta, "benchmarks": bench_quant.run_pr8()}
    with open(BENCH8_JSON, "w") as f:
        json.dump(payload8, f, indent=2)
    print(f"[bench] wrote {BENCH8_JSON}")
    print("== multi-tenant fairness / paging / isolation (PR 10) ==")
    payload10 = {"meta": meta, "benchmarks": bench_tenancy.run()}
    with open(BENCH10_JSON, "w") as f:
        json.dump(payload10, f, indent=2)
    print(f"[bench] wrote {BENCH10_JSON}")
    print("== table1: complexity/guarantees ==")
    table1_complexity.run()
    print("== fig1: guarantee validation (adversarial) ==")
    fig1_guarantee.run()
    print("== fig2: synthetic gaussian ==")
    fig23_synthetic.run("gaussian")
    print("== fig3: synthetic uniform ==")
    fig23_synthetic.run("uniform")
    print("== fig4: real-world proxy (MF embeddings) ==")
    fig4_real.run()
    print("== pull-loop roofline (results/roofline.md) ==")
    md = roofline.table(payload7["roofline"])
    res_dir = os.path.join(_ROOT, "results")
    os.makedirs(res_dir, exist_ok=True)
    with open(os.path.join(res_dir, "roofline.md"), "w") as f:
        f.write("# Pull-loop roofline (v5e constants, row vs coord)\n\n")
        f.write(md + "\n")
    print(md)


if __name__ == '__main__':
    main()
