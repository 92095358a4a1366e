"""One whole fit job as ``pipelines.<config>.run`` defines it: featurize
the training rows, solve, then featurize and score the rows it evaluates
(MnistRandomFFT evaluates train and test, TimitPipeline the test rows)."""

from benchmark.ops import shapes


def count(config: dict, traffic: dict) -> dict:
    n, n_test = config["n_train"], config["n_test"]
    evaluated = n_test + (n if "num_ffts" in config else 0)
    feat, app = shapes.featurize_row(config), shapes.apply_row(config)
    sol = shapes.solve(config, n)
    per_row = lambda part: part["gemm_flops"] + part["other_flops"]  # noqa: E731
    return {
        "flops": (n + evaluated) * per_row(feat) + per_row(sol)
        + evaluated * per_row(app),
        "bytes": (n + evaluated) * feat["bytes"] + sol["bytes"]
        + evaluated * app["bytes"],
    }
