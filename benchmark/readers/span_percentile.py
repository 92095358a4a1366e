"""A percentile of the durations of the program's spans of one name
(``obs/tracer.py``; the traced run turns the tracer on), times ``scale``."""

import numpy as np


def read(params: dict, run):
    durations = [end - start for name, start, end, _ in run.spans
                 if name == params["span"]]
    if not durations:
        return None
    return params.get("scale", 1.0) * float(
        np.percentile(durations, params["q"])
    )
