"""Chains the optimizer puts ONE node in place of, ahead of segment
planning. Each rule matches operator types and scalars and reads no array;
a graph without the chain's head comes back as it was given.

**The convolution chain**: ``Convolver`` → ``SymmetricRectifier`` →
sum-``Pooler`` with the convolution's output kept on the chip.
Pipelines write the chain as the reference does (``Convolver.and_then(
SymmetricRectifier).and_then(Pooler)``, RandomPatchCifar.scala:72-83);
:class:`ConvChainRule` recognises it in the optimized graph
and puts :class:`ConvRectifyPool` in its place:
one member whose output is the pooled features, so segment dispatch prices a
row at 0.3 MB and not at the 88 MB the three outputs take at 10,000 filters.
The node runs ``ops/conv_rectify_pool.py`` where that kernel can run — the
TPU backend, shapes its tiling admits — and the three bodies anywhere else:
the same values, chosen from what the code can observe, with no option.

**The sampling pass**: ``SIFTExtractor`` → nodes that act column by column
(``column_wise``: the projection) → ``ColumnSampler``. Sampling columns
commutes with everything that reads no other column, so
:class:`SampledSIFTRule` puts :class:`SampledSIFTExtractor` at the sampler's
place: it draws the sampler's columns first and makes only those descriptors
(``SIFTExtractor.sampled_batch``), then applies the column-wise nodes to the
sample. How the columns are read off the pooled maps follows from the
sample's share of the keypoint grid, from shapes alone
(``SIFTExtractor.sampled_path``): 651 of an image's 73,505 in ``voc_fv256``
(0.9%) as one gather of 16 eight-float bins a column, 1,220 of 13,436 in
``imagenet_fv16`` (9.1%) as one take of 128-float rows from the grid's stack
of raw bins.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...ops import conv_rectify_pool as _kernel
from ...workflow.graph import Graph, NodeId
from ...workflow.rules import Annotations, Rule
from ...workflow.transformer import Transformer
from ..stats.core import ColumnSampler, RowKeyedTransformer
from .core import Convolver, Pooler, SymmetricRectifier
from .sift import SIFTExtractor


class ConvRectifyPool(Transformer):
    """``pooler(rectifier(conv(X)))`` for a sum-pool with no pixel map."""

    def __init__(
        self, conv: Convolver, rectifier: SymmetricRectifier, pooler: Pooler
    ):
        self.conv = conv
        self.rectifier = rectifier
        self.pooler = pooler

    @property
    def label(self) -> str:
        return "»".join(
            op.label for op in (self.conv, self.rectifier, self.pooler)
        )

    def kernel_mode(self, shape: Tuple[int, ...]) -> Optional[str]:
        """How the fused kernel runs a batch of this shape (``"compiled"``),
        or None where the three bodies run: off the TPU, a batch that is
        not (n, x, y, channels) images, shapes the tiling does not admit."""
        mode = _kernel.kernel_mode()
        S, C = self.conv.conv_size, self.conv.img_channels
        if mode is None or len(shape) != 4 or shape[3] != C or not shape[0]:
            return None
        fits = _kernel.supported(
            shape[1] - S + 1, shape[2] - S + 1, S * S * C,
            self.conv.filters.shape[0], self.pooler.stride,
            self.pooler.pool_size,
        )
        return mode if fits else None

    def row_scratch_bytes(self, shape: Tuple[int, ...]) -> int:
        """HBM bytes an image holds ahead of the kernel besides the node's
        output — segment dispatch prices a row by it (``compile/segment.py:
        _item_bytes``) — or 0 where the bodies run."""
        if self.kernel_mode(shape) is None:
            return 0
        S = self.conv.conv_size
        return _kernel.scratch_bytes(
            shape[1] - S + 1, shape[2] - S + 1,
            self.pooler.stride, self.pooler.pool_size,
        )

    def segment_facts(self, shape: Tuple[int, ...], rows: int) -> dict:
        """What ``exec.segment`` says of ``rows`` images of ``shape``
        through this node: their count where the fused kernel runs them."""
        fused = self.kernel_mode(shape) is not None
        return {"conv_fused_rows": rows} if fused else {}

    def trace_batch(self, X):
        mode = self.kernel_mode(X.shape)
        if mode is None:
            return self.pooler.trace_batch(
                self.rectifier.trace_batch(self.conv.trace_batch(X))
            )
        return self.fused(X, interpret=mode == "interpret")

    def fused(self, X, *, interpret: bool = False):
        """The chain through the kernel: the Convolver's operands, window
        moments and bias as its own body takes them, the rest on the tile."""
        conv = self.conv
        with jax.named_scope("ks.featurize.conv_rectify_pool"):
            X, filters = conv._operands(X)
            inv_sd = None
            if conv.normalize_patches:
                inv_sd = (1.0 / conv._patch_sd(X))[..., 0]
            return _kernel.conv_rectify_pool(
                X, filters, inv_sd, conv._bias(), patch=conv.conv_size,
                alpha=self.rectifier.alpha, max_val=self.rectifier.max_val,
                stride=self.pooler.stride, pool_size=self.pooler.pool_size,
                interpret=interpret,
            )


def _heads(graph: Graph, kind) -> List[NodeId]:
    return [
        n for n in sorted(graph.nodes)
        if isinstance(graph.get_operator(n), kind)
    ]


def _only_reader_of(
    graph: Graph, annotations: Annotations
) -> Callable[[NodeId], Optional[NodeId]]:
    """``only_reader(node)``: the one node that reads ``node``, or None
    where a second node or a sink reads it too, or its result is saved
    (annotated: it has to reach the state table)."""
    readers: dict = {}
    for node in graph.nodes:
        for d in graph.get_dependencies(node):
            readers.setdefault(d, []).append(node)
    for d in graph.sink_dependencies.values():
        readers.setdefault(d, []).append(None)

    def only_reader(node: NodeId) -> Optional[NodeId]:
        found = readers.get(node, [])
        if len(found) != 1 or node in annotations:
            return None
        return found[0]

    return only_reader


def _replace_chain(
    graph: Graph, chain: Sequence[NodeId], fused: Transformer
) -> Graph:
    """``fused`` at the last node of ``chain``, reading what its first node
    read; the nodes before the last go."""
    graph = graph.set_operator(chain[-1], fused)
    graph = graph.set_dependencies(
        chain[-1], graph.get_dependencies(chain[0])
    )
    for node in reversed(chain[:-1]):
        graph = graph.remove_node(node)
    return graph


class ConvChainRule(Rule):
    """Replace every ``Convolver`` whose only reader is a
    ``SymmetricRectifier`` whose only reader is a ``Pooler`` with
    ``pool_fn == "sum"`` and no ``pixel_fn`` by one :class:`ConvRectifyPool`
    at the Pooler's node. Matches operator types and scalars; reads no
    array. A chain with a saved (annotated) interior result is left as
    written: that result has to reach the state table."""

    name = "ConvChainRule"

    def apply(
        self, graph: Graph, annotations: Annotations
    ) -> Tuple[Graph, Annotations]:
        heads = _heads(graph, Convolver)
        if not heads:
            return graph, annotations
        only_reader = _only_reader_of(graph, annotations)

        def reader(node: Optional[NodeId], kind) -> Optional[NodeId]:
            found = None if node is None else only_reader(node)
            if found is None:
                return None
            return found if isinstance(graph.get_operator(found), kind) else None

        for head in heads:
            mid = reader(head, SymmetricRectifier)
            tail = reader(mid, Pooler)
            if tail is None:
                continue
            pooler = graph.get_operator(tail)
            if pooler.pool_fn != "sum" or pooler.pixel_fn is not None:
                continue
            fused = ConvRectifyPool(
                graph.get_operator(head), graph.get_operator(mid), pooler
            )
            graph = _replace_chain(graph, (head, mid, tail), fused)
        return graph, annotations


class SampledSIFTExtractor(RowKeyedTransformer):
    """``sampler(then[-1](… then[0](sift(X))))`` for nodes ``then`` that
    act column by column: the sampler's draw first, the descriptors at the
    drawn columns alone, then the column-wise nodes on the sample. The same
    columns of the same descriptors as the chain written out, whose
    normalized, quantized and transposed (128, N) matrix of an image is
    never built. Which of ``SIFTExtractor.sampled_batch``'s two bodies
    reads the columns off the pooled maps follows from the sample's share
    of the keypoint grid (``SIFTExtractor.sampled_path``, shapes alone):
    651 of 73,505 (0.9%, ``voc_fv256``) are gathered bin by bin, and the
    (N, 128) stack — 37.6 MB at 500 × 375 — is never built either; 1,220
    of 13,436 (9.1%, ``imagenet_fv16``) are taken from the grid's raw
    stack."""

    #: dispatched as the chain it stands for was, as a compiled segment in
    #: row slices, even where it is the segment's only member
    #: (``compile/segment.py:bind_segment``): node dispatch would run the
    #: body operation by operation over a whole data set
    binds_alone = True

    def __init__(
        self, sift: SIFTExtractor, then: Sequence[Transformer],
        sampler: ColumnSampler,
    ):
        self.sift = sift
        self.then = tuple(then)
        self.sampler = sampler

    def row_scratch_bytes(self, shape: Tuple[int, ...]) -> int:
        """What an image holds besides the sample while it is made
        (``compile/segment.py:_item_bytes``): 34.9 MB at 500 × 375."""
        return self.sift.sampled_scratch_bytes(shape, self.sampler.num_samples)

    def segment_facts(self, shape: Tuple[int, ...], rows: int) -> dict:
        """What ``exec.segment`` says of ``rows`` images of ``shape``
        through this node: their count, and the body that made the sample."""
        path = self.sift.sampled_path(
            shape[1], shape[2], self.sampler.num_samples
        )
        return {"sift_sampled_rows": rows, "sift_sampled_path": path}

    def trace_batch(self, X, rows=None):
        n, xd, yd = X.shape[:3]
        if rows is None:
            rows = jnp.arange(n)
        columns = self.sampler.columns(
            rows, self.sift.num_descriptors(xd, yd)
        )
        D = self.sift.sampled_batch(X, columns)
        for node in self.then:
            D = node.trace_batch(D)
        return D


class SampledSIFTRule(Rule):
    """Replace every ``SIFTExtractor`` whose only reader leads, through
    ``column_wise`` nodes each with one reader, to a ``ColumnSampler`` by
    one :class:`SampledSIFTExtractor` at the sampler's node. Matches
    operator types and one class attribute; reads no array. A second
    reader anywhere before the sampler, a saved (annotated) interior result
    or any other node in between (a ``Cacher``) leaves the chain as
    written."""

    name = "SampledSIFTRule"

    def apply(
        self, graph: Graph, annotations: Annotations
    ) -> Tuple[Graph, Annotations]:
        heads = _heads(graph, SIFTExtractor)
        if not heads:
            return graph, annotations
        only_reader = _only_reader_of(graph, annotations)
        for head in heads:
            chain = [head]
            while True:
                node = only_reader(chain[-1])
                if node is None:
                    break
                chain.append(node)
                op = graph.get_operator(node)
                if not getattr(op, "column_wise", False):
                    break
            ops = [graph.get_operator(n) for n in chain]
            if len(chain) < 2 or not isinstance(ops[-1], ColumnSampler):
                continue
            fused = SampledSIFTExtractor(ops[0], ops[1:-1], ops[-1])
            graph = _replace_chain(graph, chain, fused)
        return graph, annotations
