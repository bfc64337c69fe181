"""The traced pass's share of its chips' bf16 peak while they were busy:
the operations an exact incremental engine needs for one pass
(`bench/flops.py`) over the sum of the chips' device busy time in that
pass (the trace's mean busy time times the chips that ran) times one
chip's peak in `bench/peaks.json`."""


def read(rec):
    tr, peak = rec.get("trace"), rec.get("peak")
    if not tr or not tr["busy_s"] or not peak or not rec.get("flops_per_pass"):
        return None
    return 100.0 * rec["flops_per_pass"] / (
        tr["busy_s"] * tr["n_devices"] * peak["bf16_flops"])
