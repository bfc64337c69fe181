"""The busiest chip's device busy time over the chips' mean, in the traced
pass: 1 where the partitioner's parts give every chip the same work. The
chips' all_to_alls hold them in step, so work one chip lacks shows as its
wait inside the collective, which counts as busy: the ratio stays near 1
and the imbalance moves `wire_ms.mesh4`."""


def read(rec):
    busy = [c["busy_s"] for c in (rec.get("spans") or {}).get("chips", [])]
    if not busy or not sum(busy):
        return None
    return max(busy) / (sum(busy) / len(busy))
