"""Device busy time per launch over the traced pass: the union of the
intervals in which an operation ran on the chip, over the pass's
launches (drain launches included)."""


def read(rec):
    tr = rec.get("trace")
    traced = [p for p in rec.get("passes", []) if p["traced"]]
    if not tr or not traced or not traced[0]["launches"]:
        return None
    return 1e3 * tr["busy_s"] / traced[0]["launches"]
