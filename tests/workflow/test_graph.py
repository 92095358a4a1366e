"""Graph surgery tests (parity: workflow/GraphSuite.scala — every op including
argument-check failure paths)."""

import pytest

from keystone_tpu.workflow.graph import Graph, GraphError, NodeId, SinkId, SourceId
from keystone_tpu.workflow.operators import Operator


class Op(Operator):
    """Minimal identity-distinct operator for structural tests."""

    def __init__(self, name):
        self.name = name

    @property
    def label(self):
        return self.name


def build_simple():
    """source -> a -> b -> sink, plus c hanging off a."""
    g = Graph()
    g, s = g.add_source()
    a, b, c = Op("a"), Op("b"), Op("c")
    g, na = g.add_node(a, [s])
    g, nb = g.add_node(b, [na])
    g, nc = g.add_node(c, [na])
    g, snk = g.add_sink(nb)
    return g, s, na, nb, nc, snk


def test_add_node_and_accessors():
    g, s, na, nb, nc, snk = build_simple()
    assert g.nodes == {na, nb, nc}
    assert g.sources == {s}
    assert g.sinks == {snk}
    assert g.get_dependencies(nb) == (na,)
    assert g.get_sink_dependency(snk) == nb
    assert g.get_operator(na).label == "a"


def test_add_node_missing_dep_fails():
    g = Graph()
    with pytest.raises(GraphError):
        g.add_node(Op("x"), [NodeId(99)])
    with pytest.raises(GraphError):
        g.add_node(Op("x"), [SourceId(0)])


def test_add_sink_missing_dep_fails():
    g = Graph()
    with pytest.raises(GraphError):
        g.add_sink(NodeId(0))


def test_get_missing_node_fails():
    g, *_ = build_simple()
    with pytest.raises(GraphError):
        g.get_operator(NodeId(99))
    with pytest.raises(GraphError):
        g.get_dependencies(NodeId(99))
    with pytest.raises(GraphError):
        g.get_sink_dependency(SinkId(99))


def test_set_dependencies_and_operator():
    g, s, na, nb, nc, snk = build_simple()
    g2 = g.set_dependencies(nb, [nc])
    assert g2.get_dependencies(nb) == (nc,)
    assert g.get_dependencies(nb) == (na,)  # original untouched (immutability)
    new_op = Op("b2")
    g3 = g.set_operator(nb, new_op)
    assert g3.get_operator(nb) is new_op
    assert g.get_operator(nb).label == "b"


def test_set_on_missing_node_fails():
    g, *_ = build_simple()
    with pytest.raises(GraphError):
        g.set_operator(NodeId(99), Op("x"))
    with pytest.raises(GraphError):
        g.set_dependencies(NodeId(99), [])
    with pytest.raises(GraphError):
        g.set_sink_dependency(SinkId(99), NodeId(0))


def test_remove_node_referenced_fails():
    g, s, na, nb, nc, snk = build_simple()
    with pytest.raises(GraphError):
        g.remove_node(na)  # b and c depend on it
    with pytest.raises(GraphError):
        g.remove_node(nb)  # sink depends on it
    g2 = g.remove_node(nc)
    assert nc not in g2.nodes


def test_remove_source_referenced_fails():
    g, s, na, *_ = build_simple()
    with pytest.raises(GraphError):
        g.remove_source(s)


def test_remove_sink_then_node():
    g, s, na, nb, nc, snk = build_simple()
    g = g.remove_sink(snk)
    g = g.remove_node(nb)
    g = g.remove_node(nc)
    g = g.remove_node(na)
    g = g.remove_source(s)
    assert not g.nodes and not g.sources and not g.sinks


def test_replace_dependency():
    g, s, na, nb, nc, snk = build_simple()
    g2 = g.replace_dependency(nb, nc)  # sink now reads c
    assert g2.get_sink_dependency(snk) == nc


def test_add_graph_disjoint_union():
    g1, s1, na1, nb1, nc1, snk1 = build_simple()
    g2, s2, na2, nb2, nc2, snk2 = build_simple()
    merged, source_map, sink_map = g1.add_graph(g2)
    assert len(merged.nodes) == 6
    assert len(merged.sources) == 2
    assert len(merged.sinks) == 2
    # remapped ids don't collide
    assert source_map[s2] != s1
    assert sink_map[snk2] != snk1
    # structure preserved under remap
    new_sink_dep = merged.get_sink_dependency(sink_map[snk2])
    assert merged.get_operator(new_sink_dep).label == "b"


def test_connect_graph_splices_sink_to_source():
    g1 = Graph()
    g1, s1 = g1.add_source()
    a = Op("a")
    g1, na = g1.add_node(a, [s1])
    g1, snk1 = g1.add_sink(na)

    g2 = Graph()
    g2, s2 = g2.add_source()
    b = Op("b")
    g2, nb = g2.add_node(b, [s2])
    g2, snk2 = g2.add_sink(nb)

    merged, source_map, sink_map = g1.connect_graph(g2, {snk1: s2})
    # spliced source and sink are gone
    assert len(merged.sources) == 1
    assert len(merged.sinks) == 1
    # b's dependency is now a
    (new_b,) = [n for n in merged.nodes if merged.get_operator(n) is b]
    (new_a,) = [n for n in merged.nodes if merged.get_operator(n) is a]
    assert merged.get_dependencies(new_b) == (new_a,)


def test_connect_graph_bad_splice_fails():
    g1, s1, na1, nb1, nc1, snk1 = build_simple()
    g2, s2, *_ = build_simple()
    with pytest.raises(GraphError):
        g1.connect_graph(g2, {SinkId(99): s2})
    with pytest.raises(GraphError):
        g1.connect_graph(g2, {snk1: SourceId(99)})


def test_to_dot_contains_structure():
    g, s, na, nb, nc, snk = build_simple()
    dot = g.to_dot()
    assert "digraph" in dot
    assert "a" in dot and "b" in dot
    assert "->" in dot
