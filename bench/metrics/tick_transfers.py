"""Tick-engine residency: device transfers of cached tick inputs
(TickEngine.resident_transfers) per tick of the window. Steady state
should move none."""


def read(rec):
    if not rec.get("ticks") or "transfers" not in rec:
        return None
    return rec["transfers"] / rec["ticks"]
