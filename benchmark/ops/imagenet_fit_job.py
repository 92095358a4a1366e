"""One whole ImageNetSiftLcsFV fit job as the algorithm needs it: every
training and held-out image through both descriptor chains once, both
codebooks' fits (PCA, k-means++, EM), the class-weighted solve, the held-out
images scored."""

from benchmark.ops import imagenet_shapes as shapes


def count(config: dict, traffic: dict):
    if not shapes.applies(config):
        return None
    feat, app = shapes.featurize_image(config), shapes.apply_row(config)
    book, sol = shapes.codebooks(config), shapes.solve(config, config["n_train"])
    whole = lambda part: part["gemm_flops"] + part["other_flops"]  # noqa: E731
    images = shapes.images_featurized(config)
    return {
        "flops": images * whole(feat) + whole(book) + whole(sol)
        + config["n_test"] * whole(app),
        "bytes": images * feat["bytes"] + book["bytes"] + sol["bytes"]
        + config["n_test"] * app["bytes"],
    }
