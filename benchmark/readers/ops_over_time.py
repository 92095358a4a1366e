"""The whole step's share of the chip's peak, in percent: the operations
the work needs from shapes (``ops/<ops>.py``) times the units done in the
window, over the window times the published peak rate."""


def read(params: dict, run):
    units, window_s = run.facts.get("units"), run.facts.get("window_s")
    if not units or not window_s:
        return None
    ops = run.manifest.ops(params["ops"])
    need = ops.count(run.config, run.traffic)
    chips = run.cell["chips"]
    return 100.0 * units * need["flops"] / (
        window_s * chips * run.peak["flops_per_s"]
    )
