"""The serving window's share (%) of the chip's peak on a RotatE owner: the
least time of every dispatched batch's work, summed over the batches that
served the window's requests, over the window's seconds. A batch's work is
one read of the entity table and every pair of its real rows against every
entity (bench/work_rotate.py), its least time the larger of operations and
bytes at bench/peaks.json's peaks. Unlike ``serve_mfu`` it does not depend
on a tail latency."""
from bench import work, work_rotate


def read(rec):
    if "batch_rows_by_kind" not in rec or not rec.get("window_s"):
        return None
    e, d = rec["entities"], rec["dim"]
    least = 0.0
    for _kind, rows in rec["batch_rows_by_kind"]:
        w = work.Work(work_rotate.cl1_pairs(rows, e, d), work.F32 * e * d)
        least += work.least_seconds(w, rec["device_kind"])[0]
    return 100.0 * least / rec["window_s"]
