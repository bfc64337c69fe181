"""Ingest rate: all edges of the untraced passes in the window over all
the time of those passes, drains included (re-zeroing excluded)."""


def read(rec):
    passes = [p for p in rec.get("passes", []) if not p["traced"]]
    if not passes:
        return None
    return sum(p["edges"] for p in passes) / sum(p["seconds"] for p in passes)
