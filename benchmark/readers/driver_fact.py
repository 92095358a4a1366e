"""A number the cell's driver took with its own clock (how late the load
generator ran, a further percentile): ``run.facts[key]``."""


def read(params: dict, run):
    value = run.facts.get(params["key"])
    return None if value is None else params.get("scale", 1.0) * value
