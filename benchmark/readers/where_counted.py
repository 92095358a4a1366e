"""The reader ``params["reader"]`` — one of those that divide a count from
shapes (``ops/<ops>.py``) by a time — where that count describes the run's
configuration. A count answers None for a configuration it does not
describe; the metric is then left out of the line, as wherever a reader
finds nothing to read."""


def read(params: dict, run):
    ops = run.manifest.ops(params["ops"])
    if ops.count(run.config, run.traffic) is None:
        return None
    return run.manifest.reader(params["reader"]).read(params, run)
