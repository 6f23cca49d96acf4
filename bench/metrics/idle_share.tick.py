"""Share of the traced tick (%) in which no operation ran on the device:
1 - (union of busy intervals on the TPU plane) / the tick's wall time. A
traced run traces the window's first tick."""


def read(rec):
    if "ticks" not in rec or "trace" not in rec:
        return None
    return 100.0 * rec["trace"]["idle_share"]
