"""Share of the traced pass in which no operation ran on the chip."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["window_s"] or "passes" not in rec:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
