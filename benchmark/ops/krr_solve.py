"""The kernel ridge solve of one RandomPatchCifarKernel fit job: the n²
kernel entries made once, the residuals, the block factorisations with
their solves, the kernel written and read once."""

from benchmark.ops import krr_shapes as shapes


def count(config: dict, traffic: dict):
    if not shapes.applies(config):
        return None
    sol = shapes.solve(config)
    return {"flops": sol["gemm_flops"] + sol["other_flops"],
            "bytes": sol["bytes"]}
