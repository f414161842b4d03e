"""collective_ms: device time of collectives per dispatch (ms), per chip."""


def read(ctx):
    t, n = ctx["trace"], ctx["window"]["dispatches"]
    if t is None or t["devices"] < 2 or not n:
        return None
    return t["collective_s"] / n * 1e3
