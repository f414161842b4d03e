"""kernel_ms: device time of the fused cascade kernel per dispatch (ms).

The Pallas custom call's device time in the traced window, per chip on
average, over the dispatches the runtime counted in that window.
"""


def read(ctx):
    t, n = ctx["trace"], ctx["window"]["dispatches"]
    if t is None or not t["kernel_ops"] or not n:
        return None
    return t["kernel_s"] / n * 1e3
