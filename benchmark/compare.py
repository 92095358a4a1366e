"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside a limit of its own.

A fit produces a model; it is held to the reference's model by what both
predict on the held-out rows (``scores_gap``) and by the test error the
job itself reported (``test_error_gap``). A scoring or a serving window
produces one class label a row; each label is held to the reference's
scores of that row (``label_gap_*``): how far the reference's score of the
label that was produced lies below the reference's best, in units of the
spread of the scores. Labels are never compared for equality: with
near-ties the largest score changes on rounding.

The limits are data: ``limits/<workload>.json``, with the readings each
was set from.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import refmath

HIGHEST = {"featurizer": "highest", "solver": "highest", "apply": "highest"}


def below(stated: dict) -> dict:
    """The control's precisions: each stated one lowered by one step."""
    return {
        part: refmath.BELOW.get(p, p) if p in refmath.PRECISIONS else p
        for part, p in stated.items()
    }


def stated(config: dict) -> dict:
    """The configuration's stated precisions, where a part states one of
    ``refmath.PRECISIONS``; a part that has no matrix product is float32."""
    return {
        part: p if p in refmath.PRECISIONS else "highest"
        for part, p in config["precision"].items()
    }


def with_limits(numbers: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every number that has a limit; a
    number that is not finite fails whatever its limit."""
    out = {}
    for name, limit in limits.items():
        value = float(numbers[name])
        out[name] = {
            "value": value if np.isfinite(value) else float("inf"),
            "limit": float(limit),
        }
    return out


def _on_device(model: dict) -> dict:
    return {k: jnp.asarray(v, jnp.float32) for k, v in model.items()}


def fit_numbers(config, reference, train, held_out, model, test_error,
                *, rows_per_block: int = 8192) -> dict:
    """``model`` and ``test_error`` are what stands in the program's place;
    ``train`` and ``held_out`` are the ``(X, y)`` the job was given, made
    anew."""
    (X_train, y_train), (X_test, y_test) = train, held_out
    ref_model = reference.fit(config, X_train, y_train, precision=HIGHEST)
    feat = reference.featurizer(config, "highest")
    kw = dict(rows_per_block=rows_per_block, precision="highest")
    S_ref = refmath.scores(feat, X_test, ref_model, **kw)
    S_got = refmath.scores(feat, X_test, model, **kw)
    ref_error = float(jnp.mean(jnp.argmax(S_ref, axis=1) != y_test))
    return {
        "scores_gap": float(
            jnp.linalg.norm(S_got - S_ref) / jnp.linalg.norm(S_ref)
        ),
        "test_error_gap": abs(float(test_error) - ref_error),
        "test_error": float(test_error),
        "reference_test_error": ref_error,
    }


def label_numbers(feat, ref_model, row_blocks, label_blocks) -> dict:
    """``row_blocks`` are the rows that were scored or served, block by
    block, ``label_blocks`` the labels the timed path gave them; ``feat``
    is the reference's ``(apply, params)`` at ``highest``."""
    apply, params = feat
    ref_model = _on_device(ref_model)

    @jax.jit
    def gaps(params, model, Xb, labels):
        S = refmath.mm(
            apply(params, Xb) - model["mean"], model["W"], "highest"
        ) + model["b"]
        picked = jnp.take_along_axis(
            S, labels[:, None].astype(jnp.int32), axis=1
        )[:, 0]
        return jnp.max(S, axis=1) - picked, jnp.sum(S), jnp.sum(S * S)

    all_gaps, total, squares, count = [], 0.0, 0.0, 0
    for Xb, labels in zip(row_blocks, label_blocks):
        g, s, ss = gaps(params, ref_model, Xb, jnp.asarray(labels))
        all_gaps.append(np.asarray(g))
        total += float(s)
        squares += float(ss)
        count += Xb.shape[0] * ref_model["W"].shape[1]
    g = np.concatenate(all_gaps)
    spread = max(squares / count - (total / count) ** 2, 0.0) ** 0.5
    return {
        "label_gap_max": float(g.max() / spread),
        "label_gap_mean": float(g.mean() / spread),
        "label_disagree_share": float(np.mean(g > 0)),
    }


def reference_labels(feat, model, row_blocks, precision: str) -> list:
    """The labels the reference gives at ``precision`` — what a control
    puts in the program's place."""
    apply, params = feat
    model = _on_device(model)

    @jax.jit
    def label(params, model, Xb):
        centred = apply(params, Xb) - model["mean"]
        S = refmath.mm(centred, model["W"], precision) + model["b"]
        return jnp.argmax(S, axis=1)

    return [np.asarray(label(params, model, Xb)) for Xb in row_blocks]
