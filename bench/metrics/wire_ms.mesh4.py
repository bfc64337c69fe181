"""Device time of the collective per launch over the traced pass: leaf
ops under the `d3.wire` scope (the all_to_all), mean over chips, over the
pass's launches (drain launches included). None where no op falls under
`d3.wire`, as in a program that has no such scope."""


def read(rec):
    red = (rec.get("spans") or {}).get("all")
    traced = [p for p in rec.get("passes", []) if p["traced"]]
    if (not red or "wire" not in red["planes_s"] or not traced
            or not traced[0]["launches"]):
        return None
    return 1e3 * red["planes_s"]["wire"] / traced[0]["launches"]
