"""One whole RandomPatchCifar fit job as ``pipelines.random_patch_cifar.run``
defines it: featurize the training images, scale, the one-pass block solve,
then featurize, scale and score the training and the held-out images."""

from benchmark.ops import cifar_shapes as shapes


def count(config: dict, traffic: dict):
    if not shapes.applies(config):
        return None
    n, scored = config["n_train"], config["n_train"] + config["n_test"]
    feat, app = shapes.featurize_image(config), shapes.apply_row(config)
    sol = shapes.solve(config, n)
    whole = lambda part: part["gemm_flops"] + part["other_flops"]  # noqa: E731
    images = shapes.images_featurized(config)
    return {
        "flops": images * whole(feat) + 2.0 * n * config["d"]  # the scaler
        + whole(sol) + scored * whole(app),
        "bytes": images * feat["bytes"] + sol["bytes"]
        + scored * app["bytes"],
    }
