"""queue_wait_p95_ms: 95th percentile of submit-to-dispatch waits (ms).

The ``queued`` spans the runtime's `SpanTracer` records in the window; the
runtime is fed ``time.perf_counter()``, so the spans are wall time.
"""

import math


def read(ctx):
    waits = sorted(ctx["queued_ms"])
    if not waits:
        return None
    return waits[max(1, math.ceil(0.95 * len(waits))) - 1]
