"""The featurizer's matrix product for one row scored."""

from benchmark.ops import shapes


def count(config: dict, traffic: dict) -> dict:
    feat = shapes.featurize_row(config)
    return {"flops": feat["gemm_flops"], "bytes": feat["bytes"]}
