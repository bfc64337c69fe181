"""The traced pass's share of the chip's bf16 peak while the chip was
busy: the operations an exact incremental engine needs for one pass
(`bench/flops.py`) over the device's busy time in that pass (from the
profiler trace) times the peak in `bench/peaks.json`."""


def read(rec):
    tr, peak = rec.get("trace"), rec.get("peak")
    if not tr or not tr["busy_s"] or not peak or not rec.get("flops_per_pass"):
        return None
    return 100.0 * rec["flops_per_pass"] / (tr["busy_s"] * peak["bf16_flops"])
