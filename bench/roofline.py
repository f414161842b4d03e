"""Least bytes a dispatch of the cascade must read, and the chips' peaks.

`least_bytes` counts, once per dispatch, the pull steps of the schedule
the executor's plan runs (every tile's pulls in every round, plus the
final-coverage pulls that make the fp32 scores exact) times the bytes of
one ``tile x block`` slab as the table is stored: the unquantized tier
at the table's own bytes per element (4 for fp32, 2 for bf16), a
quantized tier at its width.  Every query of the
batch needs at least those tiles, so any implementation of the dispatch
reads at least this much, however it shares pulls across the batch; and
no schedule pulls a (tile, block) cell twice, so the count never exceeds
one scan of the table.

Peaks are per chip, keyed by JAX's ``device_kind``.  Source: Google Cloud
documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; unknown kinds are an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add "
                       f"them to bench/roofline.py with their source") from None


def slab_bytes(tile: int, block: int, precision: str,
               pq_subdims: int = 8, table_itemsize: int = 4) -> float:
    """Bytes of one ``tile x block`` table slab as the kernel reads it.

    ``table_itemsize`` is the stored table's bytes per element, which
    the unquantized tier (``fp32``) reads as it is.
    """
    per_row = {"fp32": float(table_itemsize) * block, "int8": 1.0 * block,
               "int4": 0.5 * block,
               "pq": float(block // pq_subdims)}[precision]
    return tile * per_row


def pull_steps(rounds, n_blocks: int, final_coverage: bool) -> int:
    """Pulls of one query's schedule: rounds plus final coverage.

    ``rounds`` are the plan's rounds (``n_arms``, ``t_new``, ``n_keep``,
    ``t_cum``); final coverage pulls the blocks the last round left
    unread for the surviving tiles.
    """
    steps = sum(r.n_arms * r.t_new for r in rounds)
    if final_coverage and rounds:
        last = rounds[-1]
        steps += last.n_keep * (n_blocks - last.t_cum)
    return steps


def least_bytes(plan, *, final_coverage: bool,
                table_itemsize: int = 4) -> float:
    """Least HBM bytes of one dispatch of ``plan`` on one chip."""
    return pull_steps(plan.schedule.rounds, plan.n_blocks,
                      final_coverage) * slab_bytes(
        plan.tile, plan.block, plan.precision, plan.pq_subdims,
        table_itemsize)


def table_scan_bytes(plan, table_itemsize: int = 4) -> float:
    """Bytes of one scan of the (padded) table at the plan's precision."""
    return plan.n_tiles * plan.n_blocks * slab_bytes(
        plan.tile, plan.block, plan.precision, plan.pq_subdims,
        table_itemsize)
