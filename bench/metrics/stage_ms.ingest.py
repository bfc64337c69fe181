"""Host staging per launch: time in `D3Pipeline._stage_super_batches`
(partitioner, batch building, upload) over the launches of the untraced
passes, drain launches included."""


def read(rec):
    passes = [p for p in rec.get("passes", []) if not p["traced"]]
    launches = sum(p["launches"] for p in passes)
    if not launches:
        return None
    return 1e3 * sum(p["stage_s"] for p in passes) / launches
