"""fused_cascade_roofline: the kernel's share of its HBM roofline (%).

Least time of one dispatch (`bench.roofline.least_bytes`, at the table's
stored bytes per element, over the chip's HBM bandwidth; the pulls are
memory-bound) over the kernel's measured device time per dispatch.
"""

from bench import roofline


def read(ctx):
    t, n = ctx["trace"], ctx["window"]["dispatches"]
    if t is None or not t["kernel_ops"] or not n or t["kernel_s"] <= 0:
        return None
    least = (roofline.least_bytes(ctx["plan"],
                                  final_coverage=ctx["final_coverage"],
                                  table_itemsize=ctx["table_itemsize"])
             / roofline.peaks(ctx["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least / (t["kernel_s"] / n)
