"""The per-block solver's matrix products in one RandomPatchCifar fit job:
the least work of the one-pass block solve with its ragged last block."""

from benchmark.ops import cifar_shapes as shapes


def count(config: dict, traffic: dict):
    if not shapes.applies(config):
        return None
    sol = shapes.solve(config, config["n_train"])
    return {"flops": sol["gemm_flops"], "bytes": sol["bytes"]}
