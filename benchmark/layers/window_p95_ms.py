"""95th percentile, send to full reply, of every request of a traced run's
window: what ``retrieve_p95_ms`` measures, read per layer in the cells where
that metric's runs spread too widely to be held to a bound (``PERF.md``
section 2). The profiler is on for ``trace_seconds`` of this window."""

import numpy as np


def read(trace, spans, counts, cell):
    lat = counts.get("window_latency_ms")
    return float(np.percentile(lat, 95)) if lat else None
