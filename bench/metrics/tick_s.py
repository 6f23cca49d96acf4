"""Federation seconds per tick: the window's wall time over the ticks
completed in it (the window ends when the tick in flight at --seconds
completes)."""


def read(rec):
    if not rec.get("ticks"):
        return None
    return rec["window_s"] / rec["ticks"]
