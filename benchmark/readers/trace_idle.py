"""The share of the time under a ``bench:`` host annotation in which no
operation ran on the chip, in percent."""


def read(params: dict, run):
    if run.reduction is None:
        return None
    share = run.reduction.idle_share_under(params["annotation"])
    return None if share is None else 100.0 * share
