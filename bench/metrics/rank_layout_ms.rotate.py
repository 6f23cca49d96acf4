"""Device time (ms) a RotatE rank call spends laying out the table's
[re | im] planes for the fused-rank kernel (the ``rank_planes`` scope of
``fused_ranks_pallas_graph``) in the traced serving window: the summed
device time of the planes op over the count of ``%fused_rank_cl1`` kernel
events. Left out when the window ran no such kernel.

The trace names an operation by its HLO text, which does not hold the
scope: the planes op is the one whose result is the table's planes,
``f32[R,W]`` with W the planes' width and R the padded entity count
(``bench/work_rotate.py`` ``is_planes_op``)."""
from bench import trace, work_rotate


def read(rec):
    if "trace" not in rec or "entities" not in rec:
        return None
    calls = trace.kernel_calls(rec["trace"], work_rotate.is_cl1_kernel)
    if not calls:
        return None
    secs = trace.kernel_seconds(
        rec["trace"],
        lambda hlo: work_rotate.is_planes_op(hlo, rec["entities"], rec["dim"]))
    return 1e3 * (secs or 0.0) / calls
