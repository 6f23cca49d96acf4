"""The whole tick's share (%) of the chip's peak: the least time of the
tick's required work (bench/work.py: PPAT, procrustes and aggregation,
the retrain epoch's rows read and written, Hit@10 scoring against the whole
table), max(FLOPs / peak FLOP/s, bytes / peak bytes/s), over the wall time
of the traced tick (the traced run's window, which traces one tick)."""
from bench import work


def read(rec):
    if not rec.get("ticks") or "tick_work" not in rec:
        return None
    least, _ = work.least_seconds(rec["tick_work"], rec["device_kind"])
    tick = rec["trace"]["window_s"] if "trace" in rec else rec["window_s"] / rec["ticks"]
    return 100.0 * least / tick
