"""Bytes of the all_to_all send buffers per launch, in MB, over all chips:
the pipeline's `StreamMetrics.wire_bytes` (a count of the program's own,
exact from the lanes' shapes) over its launches, drains included, in the
timed passes."""


def read(rec):
    wire = rec.get("wire") or []
    launches = sum(p["launches"] for p in wire)
    if not launches:
        return None
    return sum(p["wire_bytes"] for p in wire) / launches / 1e6
