"""Serving front door: query rows per dispatched batch over the window
(KGEServingTier stats: served / batches)."""


def read(rec):
    if not rec.get("batches"):
        return None
    return rec["served"] / rec["batches"]
