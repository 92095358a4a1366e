"""One whole RandomPatchCifarKernel fit job: featurize every image once,
scale, the kernel ridge solve, the held-out kernel rows, both scorings."""

from benchmark.ops import krr_shapes as shapes


def count(config: dict, traffic: dict):
    if not shapes.applies(config):
        return None
    return shapes.job(config)
