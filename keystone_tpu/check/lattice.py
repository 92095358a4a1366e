"""The traceability lattice: a STATIC verdict per operator.

KeystoneML learns "can this node compile?" by attempting traces; here the
verdict is derived from static evidence — the operator class registry,
the ``trace_batch`` attribute, and inspection of the function's code
objects (closure cells and nested functions included) for host-callback
markers and Python-side state mutation. The dynamic paths
(``FittedPipeline.untraceable_nodes``, strict compile, AOT export) assert
against this verdict instead of discovering it.

Verdicts, worst-first::

    opaque        no trace_batch at all: host per-item work (text
                  featurizers, ragged image loaders). Cannot jit, cannot
                  export; blocks whole-chain compilation.
    stateful      trace_batch mutates Python-side state (self.x = ...):
                  jit would freeze or silently fork that state.
    host_callback trace_batch routes through jax.pure_callback /
                  io_callback: it jits (the callback stays on host) but
                  can NOT export to a serialized StableHLO artifact.
    batch_coupled trace_batch couples rows (whole-batch statistics):
                  compiles AND exports, but must never be served through
                  any pad-and-slice path and must not stream per-chunk.
    traceable     pure jax over the stacked array: compiles, exports,
                  fuses, shards.

Classification is evidence-based and conservative in the directions that
matter: a marker we cannot rule out (callback name referenced anywhere in
the function's code graph) downgrades the verdict, and an operator class
can pin its verdict explicitly (``check_verdict = "stateful"`` or
:func:`register_verdict`) when inspection cannot see the truth.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Optional, Set

logger = logging.getLogger(__name__)

# -- the lattice ------------------------------------------------------------

TRACEABLE = "traceable"
BATCH_COUPLED = "batch_coupled"
HOST_CALLBACK = "host_callback"
STATEFUL = "stateful"
OPAQUE = "opaque"

#: worst-first severity order (index = badness rank)
SEVERITY = (OPAQUE, STATEFUL, HOST_CALLBACK, BATCH_COUPLED, TRACEABLE)

VERDICTS = frozenset(SEVERITY)


def blocks_jit(verdict: str) -> bool:
    """Does this verdict block building the whole-chain jitted function?
    (the NotTraceableError criterion)"""
    return verdict in (OPAQUE, STATEFUL)


def blocks_export(verdict: str) -> bool:
    """Does this verdict block AOT export (serialized StableHLO)?
    Host callbacks jit fine but cannot cross the export boundary."""
    return verdict in (OPAQUE, STATEFUL, HOST_CALLBACK)


# -- explicit registry ------------------------------------------------------

_VERDICT_OVERRIDES: Dict[type, str] = {}


def register_verdict(op_class: type, verdict: str) -> None:
    """Pin the verdict for every node of ``op_class`` — the escape hatch
    for operators whose code inspection cannot see the truth (native
    extensions, generated wrappers)."""
    if verdict not in VERDICTS:
        raise ValueError(f"unknown verdict {verdict!r}")
    _VERDICT_OVERRIDES[op_class] = verdict


# -- code inspection --------------------------------------------------------

#: names whose presence anywhere in a trace function's code graph marks it
#: as host-callback-routed
_CALLBACK_MARKERS = frozenset({
    "pure_callback",
    "io_callback",
    "host_callback",
    "call_tf",
    "debug_callback",
})


def _iter_code_graph(fn: Any, max_depth: int = 6):
    """Yield the code objects reachable from ``fn``: its own code, nested
    code constants (comprehensions, local defs), closure-cell functions,
    and — bounded to this package — global functions it references by
    name. Global chasing stops at the keystone_tpu boundary so inspecting
    a node never walks into jax/numpy internals."""
    seen: Set[int] = set()
    stack = [(fn, 0)]
    while stack:
        obj, depth = stack.pop()
        code = getattr(obj, "__code__", None)
        if code is None or id(code) in seen or depth > max_depth:
            continue
        seen.add(id(code))
        yield code
        # nested code objects (lambdas, comprehensions, inner defs)
        for const in code.co_consts:
            if hasattr(const, "co_names"):
                # wrap a bare code object so the stack stays uniform
                stack.append((_CodeHolder(const), depth + 1))
        # closure cells holding functions
        for cell in getattr(obj, "__closure__", None) or ():
            try:
                cv = cell.cell_contents
            except ValueError:  # empty cell
                continue
            if callable(cv):
                stack.append((cv, depth + 1))
        # referenced globals that are keystone-local functions
        g = getattr(obj, "__globals__", None)
        if g is not None:
            for name in code.co_names:
                target = g.get(name)
                if (
                    callable(target)
                    and getattr(target, "__module__", "").startswith(
                        "keystone_tpu"
                    )
                    and hasattr(target, "__code__")
                ):
                    stack.append((target, depth + 1))


class _CodeHolder:
    """Adapter presenting a bare code object with the function surface
    ``_iter_code_graph`` walks."""

    __slots__ = ("__code__",)

    def __init__(self, code):
        self.__code__ = code


def _mentions_callback(fn: Any) -> bool:
    for code in _iter_code_graph(fn):
        if _CALLBACK_MARKERS & set(code.co_names):
            return True
    return False


def _mutates_self(fn: Any) -> bool:
    """Does ``fn``'s OWN code assign attributes on its first positional
    argument (``self.x = ...``)? Source-level AST when available; absent
    source (built/frozen), no evidence ⇒ not stateful."""
    import ast
    import inspect
    import textwrap

    raw = getattr(fn, "__func__", fn)
    code = getattr(raw, "__code__", None)
    if code is None or not code.co_varnames:
        return False
    self_name = code.co_varnames[0]
    if self_name not in ("self", "cls"):
        return False
    try:
        tree = ast.parse(textwrap.dedent(inspect.getsource(raw)))
    except (OSError, SyntaxError, TypeError):
        return False
    for node in ast.walk(tree):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for t in targets:
            if (
                isinstance(t, ast.Attribute)
                and isinstance(t.value, ast.Name)
                and t.value.id == self_name
            ):
                return True
    return False


# -- classification ---------------------------------------------------------

#: bounded memo keyed on (op class, trace_batch CODE OBJECT, coupling) —
#: classification is pure in those inputs, and a pipeline instantiates
#: many nodes per class. The code object itself is the key (not its id):
#: holding the reference prevents a GC'd function's recycled id from
#: serving a stale verdict to an unrelated new function.
from collections import OrderedDict

_CLASS_MEMO: "OrderedDict[tuple, str]" = OrderedDict()
_CLASS_MEMO_MAX = 256


def classify(op: Any) -> str:
    """The static verdict for one operator instance."""
    from ..workflow.operators import GatherTransformerOperator

    cls = type(op)
    if cls in _VERDICT_OVERRIDES:
        return _VERDICT_OVERRIDES[cls]
    declared = getattr(op, "check_verdict", None)
    if declared is not None:
        if declared not in VERDICTS:
            raise ValueError(
                f"{cls.__name__}.check_verdict={declared!r} is not a "
                f"lattice verdict {sorted(VERDICTS)}"
            )
        return declared

    if isinstance(op, GatherTransformerOperator):
        return TRACEABLE  # structural zip: identity inside a traced fn

    fn = getattr(op, "trace_batch", None)
    if fn is None:
        return OPAQUE

    # memoize ONLY closure-free functions: classification walks closure
    # cells, so two functions sharing one code object but closing over
    # different helpers (a factory-made batch_fn wrapping a pure-jax vs a
    # callback-routed f) can have DIFFERENT true verdicts — a closure is
    # exactly the part the code-object key cannot see
    if getattr(fn, "__closure__", None):
        memo_key = cached = None
    else:
        memo_key = (cls, getattr(fn, "__code__", None), bool(
            getattr(op, "batch_coupled", False)
        ))
        try:
            cached = _CLASS_MEMO.get(memo_key)
        except TypeError:  # unhashable exotic callable
            memo_key = cached = None
    if cached is not None:
        _CLASS_MEMO.move_to_end(memo_key)
        return cached

    if _mutates_self(fn):
        verdict = STATEFUL
    elif _mentions_callback(fn):
        verdict = HOST_CALLBACK
    elif getattr(op, "batch_coupled", False):
        verdict = BATCH_COUPLED
    else:
        verdict = TRACEABLE
    if memo_key is not None:
        _CLASS_MEMO[memo_key] = verdict
        while len(_CLASS_MEMO) > _CLASS_MEMO_MAX:
            _CLASS_MEMO.popitem(last=False)
    return verdict
