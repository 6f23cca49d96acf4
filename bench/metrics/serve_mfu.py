"""One request's share (%) of the chip's peak: the least time its work can
take (one read of the entity table against 3 E d operations, bench/work.py)
over the traced run's p95 latency."""
from bench import work
from bench.harness import nearest_rank


def read(rec):
    if "latency_ms" not in rec:
        return None
    p95 = nearest_rank(rec["latency_ms"], 0.95)
    if p95 is None:
        return None
    p95_s = p95 / 1e3
    least, _ = work.least_seconds(rec["request_work"], rec["device_kind"])
    return 100.0 * least / p95_s
