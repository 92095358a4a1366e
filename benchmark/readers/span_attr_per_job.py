"""An attribute of the program's spans totalled over the traced window, a
job, over what the configuration says a job needs: the sum of ``attr`` over
the spans named ``span`` (those whose ``label`` attribute holds
``label_has``, where that is given), over the number of ``root`` spans
times ``Σ config[key] · weight`` of ``per_job``. Where the program keeps no
such spans or attributes — a commit from before them — or the configuration
lacks a key, the reader finds nothing to read."""

from benchmark.readers import span_idle


def read(params: dict, run):
    spans = span_idle.program_spans()
    if not spans:
        return None
    jobs = sum(1 for sp in spans if sp.name == params["root"])
    wanted = params.get("label_has")
    values = []
    for sp in spans:
        attrs = getattr(sp, "attrs", None) or {}
        if sp.name != params["span"] or params["attr"] not in attrs:
            continue
        if wanted and wanted not in str(attrs.get("label", "")):
            continue
        values.append(float(attrs[params["attr"]]))
    if not jobs or not values:
        return None
    if any(key not in run.config for key in params["per_job"]):
        return None
    need = sum(run.config[key] * w for key, w in params["per_job"].items())
    return sum(values) / (jobs * need) if need else None
