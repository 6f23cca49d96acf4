"""95th-percentile request latency (ms) over every request of the window,
measured as for serve_p50_ms."""
from bench.harness import nearest_rank


def read(rec):
    if "latency_ms" not in rec:
        return None
    return nearest_rank(rec["latency_ms"], 0.95)
