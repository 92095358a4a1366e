"""The descriptor chain (gray → SIFT → PCA → Fisher vector →
normalisations) over the images one fit job has to featurize: every
training and every held-out image once."""

from benchmark.ops import voc_shapes as shapes


def count(config: dict, traffic: dict):
    if not shapes.applies(config):
        return None
    feat, images = shapes.featurize_image(config), shapes.images_featurized(config)
    return {
        "flops": images * (feat["gemm_flops"] + feat["other_flops"]),
        "bytes": images * feat["bytes"],
    }
