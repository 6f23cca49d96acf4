"""Share of the traced serving window (%) in which no operation ran on the
device: 1 - (union of busy intervals on the TPU plane) / window."""


def read(rec):
    if "latency_ms" not in rec or "trace" not in rec:
        return None
    return 100.0 * rec["trace"]["idle_share"]
