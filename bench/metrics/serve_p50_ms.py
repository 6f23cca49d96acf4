"""Median request latency (ms) over every request of the window, from its
scheduled send time until its result is on the host; a request that failed,
was shed or never answered counts as infinitely late."""
from bench.harness import nearest_rank


def read(rec):
    if "latency_ms" not in rec:
        return None
    return nearest_rank(rec["latency_ms"], 0.50)
