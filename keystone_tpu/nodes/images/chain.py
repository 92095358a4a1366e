"""The convolution chain as one node: ``Convolver`` → ``SymmetricRectifier``
→ sum-``Pooler`` with the convolution's output kept on the chip.

Pipelines write the chain as the reference does (``Convolver.and_then(
SymmetricRectifier).and_then(Pooler)``, RandomPatchCifar.scala:72-83);
:class:`ConvChainRule` recognises it in the optimized graph, ahead of
segment planning, and puts :class:`ConvRectifyPool` in its place:
one member whose output is the pooled features, so segment dispatch prices a
row at 0.3 MB and not at the 88 MB the three outputs take at 10,000 filters.
The node runs ``ops/conv_rectify_pool.py`` where that kernel can run — the
TPU backend, shapes its tiling admits — and the three bodies anywhere else:
the same values, chosen from what the code can observe, with no option.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from ...ops import conv_rectify_pool as _kernel
from ...workflow.graph import Graph, NodeId
from ...workflow.rules import Annotations, Rule
from ...workflow.transformer import Transformer
from .core import Convolver, Pooler, SymmetricRectifier


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
        heads = [
            n for n in sorted(graph.nodes)
            if isinstance(graph.get_operator(n), Convolver)
        ]
        if not heads:
            return graph, annotations
        readers = {}
        for node in graph.nodes:
            for d in graph.get_dependencies(node):
                readers.setdefault(d, []).append(node)
        for d in graph.sink_dependencies.values():
            readers.setdefault(d, []).append(None)

        def only_reader(node: NodeId, kind) -> Optional[NodeId]:
            found = readers.get(node, [])
            if len(found) != 1 or found[0] is None or node in annotations:
                return None
            op = graph.get_operator(found[0])
            return found[0] if isinstance(op, kind) else None

        for head in heads:
            mid = only_reader(head, SymmetricRectifier)
            tail = only_reader(mid, Pooler) if mid is not None else None
            if tail is None:
                continue
            pooler = graph.get_operator(tail)
            if pooler.pool_fn != "sum" or pooler.pixel_fn is not None:
                continue
            fused = ConvRectifyPool(
                graph.get_operator(head), graph.get_operator(mid), pooler
            )
            graph = graph.set_operator(tail, fused)
            graph = graph.set_dependencies(
                tail, graph.get_dependencies(head)
            )
            graph = graph.remove_node(mid).remove_node(head)
        return graph, annotations
