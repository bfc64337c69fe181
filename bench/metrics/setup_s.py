"""Set-up: process start to the first timed launch (loading, warm-up,
and in a run that compiles, compilation)."""


def read(rec):
    return rec.get("setup_s")
