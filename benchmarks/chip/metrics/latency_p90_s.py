"""90th percentile of request latency over every request of the window."""

from harness import percentile


def read(w):
    return percentile(w.latencies, 90) if w.latencies else None
