"""Median request latency at the client, send to last response byte."""

from harness import percentile


def read(w):
    return percentile(w.latencies, 50) if w.latencies else None
