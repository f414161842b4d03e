"""driver_ms: device time per dispatch of the flush outside the kernel (ms).

Ops of the executor's flush program that are neither the kernel nor a
collective: padding, the tile-major relayout of the table, the rescore
and the merge.  Per chip on average.
"""


def read(ctx):
    t, n = ctx["trace"], ctx["window"]["dispatches"]
    if t is None or not t["kernel_ops"] or not n:
        return None
    return t["driver_s"] / n * 1e3
