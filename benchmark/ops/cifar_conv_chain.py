"""The convolution chain (conv → rectify → pool) over every image one fit
job featurizes; the filter bank read once a pass over a set of images."""

from benchmark.ops import cifar_shapes as shapes


def count(config: dict, traffic: dict):
    if not shapes.applies(config):
        return None
    feat, images = shapes.featurize_image(config), shapes.images_featurized(config)
    return {
        "flops": images * (feat["gemm_flops"] + feat["other_flops"]),
        "bytes": images * feat["bytes"] + 3 * shapes.filter_bank_bytes(config),
    }
