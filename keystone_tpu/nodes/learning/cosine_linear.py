"""Cosine random features and the fitted linear map over them as ONE node.

TimitPipeline (TimitPipeline.scala:44-51) writes the chain as the reference
does: ``numCosines`` × ``CosineRandomFeatures`` → gather →
``VectorCombiner`` → ``BlockLeastSquaresEstimator``. Once fitted, that is
cosine branches → gather → combiner → ``BlockLinearMapper``, and its
features — 16,384 float32 a row at ``timit_cos4``'s widths — are what the
map reads back from HBM. :class:`CosineScoresRule` puts
:class:`CosineLinearMapper` in the mapper's place in the FITTED graph
(``workflow/pipeline.py:Pipeline._fit``: the optimizer runs before the
estimator is fitted, so no optimizer rule can see the mapper). The node
runs ``ops/cosine_scores.py`` where that kernel can run — one TPU chip,
widths its tiling admits, float32 parameters, and rows whose every cosine
argument provably lies within ``bounded_cos.LIMIT`` — and the bodies
written out anywhere else: the same values, chosen from what the code can
observe, with no option.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...ops import cosine_scores as _kernel
from ...ops.bounded_cos import LIMIT
from ...workflow.graph import Graph
from ...workflow.operators import GatherTransformerOperator
from ...workflow.rules import Annotations, Rule, only_reader_of, replace_chain
from ..stats.core import CosineRandomFeatures, _argument_bound
from ..util.core import VectorCombiner
from .linear import BlockLinearMapper

#: rows a step of the bodies written out takes where the guard fails on the
#: chip: their features (16,384 float32 a row at ``timit_cos4``'s widths)
#: exist a block of rows at a time, never for a whole chunk
_EXACT_ROWS = 4096


class CosineLinearMapper(BlockLinearMapper):
    """``mapper(concat(branch(X) for branch in branches))`` for cosine
    branches that read the same rows. The mapper's ``xs``, ``b`` and
    ``feature_means`` are kept as fitted, so whoever reads a fitted
    ``BlockLinearMapper`` reads the same model here."""

    def __init__(
        self, branches: Sequence[CosineRandomFeatures],
        mapper: BlockLinearMapper,
    ):
        # the fitted mapper's parameters as they are: no second fetch
        self.branches = tuple(branches)
        self.solver_state = mapper.solver_state
        self.xs = mapper.xs
        self.block_size = mapper.block_size
        self.b = mapper.b
        self.feature_means = mapper.feature_means
        self._W = mapper._W
        self._mean = mapper._mean

    def kernel_mode(self, shape: Tuple[int, ...]) -> Optional[str]:
        """How the fused kernel runs rows of this shape (``"compiled"``),
        or None where the bodies written out run: off the TPU, rows that
        are not (n, dim), parameters that are not float32, widths the
        tiling does not admit."""
        mode = _kernel.kernel_mode()
        if mode is None or len(shape) != 2 or not shape[0]:
            return None
        params = [a for br in self.branches for a in (br.W, br.b)]
        params += [self._W] + [a for a in (self._mean, self.b) if a is not None]
        if any(a.dtype != np.float32 for a in params):
            return None
        fits = _kernel.supported(shape[1], *self._W.shape)
        return mode if fits else None

    def row_scratch_bytes(self, shape: Tuple[int, ...]) -> int:
        """HBM bytes a row holds besides the scores — segment dispatch
        prices a row by it (``compile/segment.py:_item_bytes``). Where the
        kernel runs, the larger of what the guard's two branches hold a
        row: the kernel's copy of rows padded to a whole tile
        (``cosine_scores.scratch_bytes``), the fallback's rows in bf16 and
        its blocks' scores — 1,760 bytes at ``timit_cos4``'s widths, where
        the TPU compiler gives the program 1,028. Where the bodies run, the
        branches' features and their join."""
        dim, (features, classes) = shape[-1], self._W.shape
        if self.kernel_mode(shape) is not None:
            return max(_kernel.scratch_bytes(dim), 2 * dim + 4 * classes)
        return 2 * 4 * features

    def segment_facts(self, shape: Tuple[int, ...], rows: int) -> dict:
        """What ``exec.segment`` says of ``rows`` rows through this node:
        their count where the cosines' guarded body is lowered
        (``cosine_bounded_rows``, as the branches say it), and where the
        fused kernel runs them (``cosine_scores_rows``)."""
        facts = {}
        if all(br.segment_facts(shape, rows) for br in self.branches):
            facts["cosine_bounded_rows"] = rows
        if self.kernel_mode(shape) is not None:
            facts["cosine_scores_rows"] = rows
        return facts

    def trace_batch(self, X):
        mode = self.kernel_mode(X.shape) if X.dtype == jnp.float32 else None
        if mode is None:
            features = [br.trace_batch(X) for br in self.branches]
            return super().trace_batch(jnp.concatenate(features, axis=-1))
        w_norm, b_max = zip(*(br._bound_terms() for br in self.branches))
        # the branches' Wᵀ side by side, ONE literal of the program: its
        # bf16 rounding is the kernel's operand and the fallback's too
        wt = np.concatenate([br.W for br in self.branches]).T
        b = np.concatenate([br.b for br in self.branches])
        with jax.named_scope("ks.apply.cosine_scores"):
            return _guarded_scores(
                X, jnp.asarray(wt), jnp.asarray(b),
                None if self._mean is None else jnp.asarray(self._mean),
                jnp.asarray(self._W),
                None if self.b is None else jnp.asarray(self.b),
                np.asarray(w_norm), np.asarray(b_max),
                interpret=mode == "interpret",
            )


def _written_out(X, wt, b, mean, model, intercept):
    """The bodies as the chain writes them — ``jnp.cos`` features, centred,
    mapped — a block of ``_EXACT_ROWS`` rows at a time; the branches'
    products as one, each feature's sum as the branch's own."""

    def block(Xb):
        F = jnp.cos(Xb @ wt + b)
        if mean is not None:
            F = F - mean
        out = F @ model
        return out if intercept is None else out + intercept

    n, dim = X.shape
    rows = min(_EXACT_ROWS, n)
    pad = -n % rows
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
    out = lax.map(block, X.reshape(-1, rows, dim))
    return out.reshape(-1, out.shape[-1])[:n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _guarded_scores(
    X, wt, b, mean, model, intercept, w_norm, b_max, *, interpret
):
    """The scores of the rows ``X``: through the fused kernel where every
    branch's cosine arguments provably lie within ``LIMIT`` (the largest of
    the branches' Cauchy–Schwarz bounds, ``stats.core._argument_bound``),
    the bodies written out with ``jnp.cos`` where not. ONE traced body for
    every node and job of a process, as ``_guarded_cosine`` is; the
    parameters are operands of it."""
    return lax.cond(
        jnp.max(_argument_bound(X, w_norm, b_max)) <= LIMIT,
        lambda *a: _kernel.cosine_scores(*a, interpret=interpret),
        _written_out,
        X, wt, b, mean, model, intercept,
    )


class CosineScoresRule(Rule):
    """Replace every ``BlockLinearMapper`` that reads a ``VectorCombiner``
    that reads a gather of ``CosineRandomFeatures`` branches — each read by
    the gather alone, all reading the same input — by ONE
    :class:`CosineLinearMapper` at the mapper's node. Matches operator
    types and scalars; reads no array. A chain with a saved (annotated)
    interior result, a mapper that carries refit state, or a subclass of
    any of the three, whose mathematics may differ, is left as written."""

    name = "CosineScoresRule"

    def apply(
        self, graph: Graph, annotations: Annotations
    ) -> Tuple[Graph, Annotations]:
        mappers = [
            n for n in sorted(graph.nodes)
            if type(graph.get_operator(n)) is BlockLinearMapper
        ]
        only_reader = None
        for mapper in mappers:
            chain = _cosine_chain(graph, mapper)
            if chain is None:
                continue
            if only_reader is None:
                only_reader = only_reader_of(graph, annotations)
            readers = [chain[-3]] * (len(chain) - 3) + list(chain[-2:])
            if any(only_reader(n) != r for n, r in zip(chain, readers)):
                continue
            branches = [graph.get_operator(n) for n in chain[:-3]]
            fused = CosineLinearMapper(branches, graph.get_operator(mapper))
            graph = replace_chain(graph, chain, fused)
            if _kernel.kernel_mode() == "compiled":
                _kernel.preload()
        return graph, annotations


def _cosine_chain(graph: Graph, mapper):
    """``(branch, …, gather, combiner, mapper)`` where the mapper's input
    is written so, else None. That each is read by the next alone is the
    caller's to check."""
    op = graph.get_operator(mapper)
    if op.solver_state is not None:
        return None
    deps = graph.get_dependencies(mapper)
    if len(deps) != 1 or deps[0] not in graph.operators:
        return None
    combiner = deps[0]
    if type(graph.get_operator(combiner)) is not VectorCombiner:
        return None
    deps = graph.get_dependencies(combiner)
    if len(deps) != 1 or deps[0] not in graph.operators:
        return None
    gather = deps[0]
    if not isinstance(graph.get_operator(gather), GatherTransformerOperator):
        return None
    branches = list(graph.get_dependencies(gather))
    if not branches or len(set(branches)) != len(branches) or any(
        b not in graph.operators
        or type(graph.get_operator(b)) is not CosineRandomFeatures
        for b in branches
    ):
        return None
    inputs = {graph.get_dependencies(b) for b in branches}
    if len(inputs) != 1 or len(next(iter(inputs))) != 1:
        return None
    return (*branches, gather, combiner, mapper)
