"""The fused-rank kernel's ``cl1`` (RotatE) share (%) of its roofline in the
traced serving window: the least time of the logical work of its calls
(every real rank query against the whole table, 7 operations a complex
component and one comparison a pair, the table, queries and filters read
once a call; bench/work_rotate.py) at bench/peaks.json's peaks, over the
summed device time of the kernel's events (``%fused_rank_cl1`` custom
calls). Left out when the window ran no such kernel.

The peaks are the chip's published ones, and its operation peak is the
MXU's: the VPU and EUP that compute the moduli have none published. Against
it the operations never bind (64 queries need about 0.56 ms, one read of the
1.96 GB table 2.4 ms), so the share reads as the kernel's bandwidth share:
the bytes it must read at 819 GB/s over the time it took."""
from bench import trace, work, work_rotate


def read(rec):
    if "trace" not in rec or "rank_queries" not in rec:
        return None
    match = work_rotate.is_cl1_kernel
    secs = trace.kernel_seconds(rec["trace"], match)
    calls = trace.kernel_calls(rec["trace"], match)
    if not secs or not calls:
        return None
    w = work_rotate.cl1_rank(rec["rank_queries"], rec["entities"], rec["dim"],
                             rec["rank_filter_ids"])
    # the table is read once per call, not once per window
    table = work.F32 * rec["entities"] * rec["dim"]
    w = work.Work(w.flops, w.bytes + (calls - 1) * table)
    least, _ = work.least_seconds(w, rec["device_kind"])
    return 100.0 * least / secs
