"""What the drivers share: the cell's data."""

from __future__ import annotations


def train_rows(run, n: int):
    """The configuration's training rows ``(X, y)`` on the device: the same
    in every run (``train_seed``), as a dataset is. The program compiles
    fitted weights into its programs as literals, so a training set drawn
    from ``--seed`` would make every run compile."""
    import jax

    return jax.block_until_ready(
        run.reference.make_rows(run.config, run.config["train_seed"], n)
    )


def seed_rows(run, n: int):
    """``n`` rows ``(X, y)`` of the same task on the device, drawn from
    ``--seed``: held-out rows, a scoring set, the rows clients send."""
    import jax

    return jax.block_until_ready(
        run.reference.make_rows(run.config, run.row_seed, n)
    )


def host_labels(y):
    """The loaders hand the program host int32 labels."""
    import numpy as np

    return np.asarray(y).astype(np.int32)


def release(state: dict) -> None:
    """Drop what the run holds of the program and its fit-once state, so
    that the reference finds the device free."""
    from keystone_tpu.workflow.env import PipelineEnv

    state.clear()
    PipelineEnv.get_or_create().reset()
