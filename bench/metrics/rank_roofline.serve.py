"""The Pallas fused-rank kernel's share (%) of its roofline in the traced
serving window: the least time of the logical work of its calls (every
real rank query against the whole table, the table, queries and filters
read once per call; bench/work.py) over the summed device time of the
kernel's events. Left out when the window ran no such kernel.

The kernel's events are the Pallas calls (``tpu_custom_call``) that return
int32 counts: on the serving path the fused-rank kernel is the only Pallas
kernel, and the top-k scan is XLA."""
from bench import trace, work


def is_rank_kernel(hlo: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in hlo and " = s32[" in hlo


def read(rec):
    if "trace" not in rec or "rank_queries" not in rec:
        return None
    secs = trace.kernel_seconds(rec["trace"], is_rank_kernel)
    calls = trace.kernel_calls(rec["trace"], is_rank_kernel)
    if not secs or not calls:
        return None
    w = work.l1_rank(rec["rank_queries"], rec["entities"], rec["dim"],
                     rec["rank_filter_ids"])
    # the table is read once per call, not once per window
    table = work.F32 * rec["entities"] * rec["dim"]
    w = work.Work(w.flops, w.bytes + (calls - 1) * table)
    least, _ = work.least_seconds(w, rec["device_kind"])
    return 100.0 * least / secs
