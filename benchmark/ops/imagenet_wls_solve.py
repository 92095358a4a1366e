"""The class-weighted solve of one ImageNetSiftLcsFV fit job, counted as
ONE piece of work whatever implements it: both Grams over the rows, the
cross terms, the residual update and k factorisations of d × d."""

from benchmark.ops import imagenet_shapes as shapes


def count(config: dict, traffic: dict):
    if not shapes.applies(config):
        return None
    sol = shapes.solve(config, config["n_train"])
    return {"flops": sol["gemm_flops"] + sol["other_flops"],
            "bytes": sol["bytes"]}
