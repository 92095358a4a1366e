"""keystone-tpu: a TPU-native ML pipeline framework.

A ground-up rebuild of the capabilities of KeystoneML (AMPLab's Spark-based
pipeline system): Transformers and Estimators compose with ``and_then`` into a
lazily-optimized dataflow DAG, but execution is jax/XLA — fitted pipelines
compile into a single fused XLA computation, solvers run on HBM-sharded arrays
with ICI collectives, featurizers are batched XLA programs over canonical
(n, X, Y, C) image batches, and hand-tiled Pallas kernels take over where
XLA's lowering is unstable (``ops/`` — e.g. the KRR Gaussian kernel block).
"""

import os as _os

#: where XLA's persistent compilation cache lives when the environment does
#: not place it (``JAX_COMPILATION_CACHE_DIR``): one fixed, git-ignored
#: directory in the checkout. The path is part of the cache key, so it is
#: never built from ``~``, a temp name, a pid or the time.
COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache",
)

#: programs that compile faster than this are not worth a disk entry;
#: ``compile.configure`` lowers it to 0 for the lifetime of an AOT cache
PERSIST_MIN_COMPILE_SECS = 0.5


def _place_compile_cache() -> None:
    """Point XLA at an on-disk compilation cache: compiles dominate
    cold-start wall time on the accelerator, and caching them across
    processes is free speed for every pipeline. ``JAX_COMPILATION_CACHE_DIR``
    places the cache and ``JAX_ENABLE_COMPILATION_CACHE=0`` turns it off —
    jax's own variables are the one way; when the first is set no code here
    (or in ``compile.configure``) sets another directory."""
    # NOTE: importing this package therefore imports jax and touches global
    # jax.config as an import side effect (no backend is initialized).
    import jax

    from .utils import env_str

    if env_str("JAX_COMPILATION_CACHE_DIR") is None:
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", PERSIST_MIN_COMPILE_SECS
    )


_place_compile_cache()

from .data.chunked import ChunkedDataset
from .data.dataset import Dataset
from .workflow import (
    Chainable,
    Estimator,
    FittedPipeline,
    FunctionNode,
    Identity,
    LabelEstimator,
    Pipeline,
    PipelineDataset,
    PipelineDatum,
    PipelineEnv,
    Transformer,
)

__version__ = "0.1.0"

__all__ = [
    "ChunkedDataset",
    "Dataset",
    "Chainable",
    "Pipeline",
    "PipelineDataset",
    "PipelineDatum",
    "PipelineEnv",
    "FittedPipeline",
    "Transformer",
    "Estimator",
    "LabelEstimator",
    "FunctionNode",
    "Identity",
    "__version__",
]
