"""batch_occupancy: requests dispatched per dispatch in the window.

The runtime's ``serve_batch_occupancy`` histogram, its sum over its
count.
"""


def read(ctx):
    total, count = ctx["window"]["occupancy"]
    return total / count if count else None
