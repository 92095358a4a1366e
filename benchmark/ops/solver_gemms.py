"""The block solver's matrix products in one fit job."""

from benchmark.ops import shapes


def count(config: dict, traffic: dict) -> dict:
    sol = shapes.solve(config, config["n_train"])
    return {"flops": sol["gemm_flops"], "bytes": sol["bytes"]}
