"""One row scored: featurize, then the fitted linear map."""

from benchmark.ops import shapes


def count(config: dict, traffic: dict) -> dict:
    feat, app = shapes.featurize_row(config), shapes.apply_row(config)
    return {
        "flops": feat["gemm_flops"] + feat["other_flops"] + app["gemm_flops"],
        "bytes": feat["bytes"] + app["bytes"],
    }
