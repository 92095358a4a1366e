"""A kernel's share of its roofline, in percent: the least time the chip
could take for the work — the larger of operations over the peak rate and
bytes over the peak bandwidth, both from shapes (``ops/<ops>.py``) times
the units of work in the traced window — over the device time of the
operations whose name matches."""

import re


def read(params: dict, run):
    if run.reduction is None:
        return None
    seconds = run.reduction.seconds_matching(re.compile(params["match"]))
    units = run.facts.get("units")
    if seconds <= 0 or not units:
        return None
    ops = run.manifest.ops(params["ops"])
    need = ops.count(run.config, run.traffic)
    least = max(
        need["flops"] / run.peak["flops_per_s"],
        need["bytes"] / run.peak["hbm_bytes_per_s"],
    )
    return 100.0 * units * least / seconds
