"""A value of the serving ``MetricsRegistry`` over the window: the change
of ``num`` between the snapshots before and after it, over the change of
``den`` where one is given. Dotted paths into ``snapshot()``."""


def _dig(snapshot: dict, path: str):
    for key in path.split("."):
        if not isinstance(snapshot, dict) or key not in snapshot:
            return None
        snapshot = snapshot[key]
    return snapshot


def _delta(run, path: str):
    if not run.registry:
        return None
    after = _dig(run.registry["after"], path)
    before = _dig(run.registry["before"], path) or 0
    return None if after is None else after - before


def read(params: dict, run):
    num = _delta(run, params["num"])
    if num is None:
        return None
    if "den" in params:
        den = _delta(run, params["den"])
        if not den:
            return None
        num = num / den
    return params.get("scale", 1.0) * num
