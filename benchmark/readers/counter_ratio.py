"""``scale · num / den`` of two of the harness's counters (``setup.*`` and
``window.*``: compile requests, persistent-cache hits and misses)."""


def read(params: dict, run):
    den = run.counters.get(params["den"])
    num = run.counters.get(params["num"])
    if not den or num is None:
        return None
    return params.get("scale", 1.0) * num / den
