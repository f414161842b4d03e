"""dispatch_ms: host time per dispatch of the executor (ms).

The executor's own ``cascade_dispatch_ms`` histogram (the call that ends
in ``block_until_ready``), its sum over its count in the window.
"""


def read(ctx):
    total, count = ctx["window"]["dispatch_ms"]
    return total / count if count else None
