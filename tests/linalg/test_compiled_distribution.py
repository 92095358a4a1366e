"""Pin the COMPILED artifact's distribution (VERDICT r3 #5).

The mesh tests in test_mesh_solvers.py assert sharding specs on *inputs*
and single≈multi agreement — but a silent all-replicated regression (every
device computing the full problem) would pass those. These tests inspect
the lowered+compiled program itself on the 8-device CPU mesh:

* operands stay 1/N-sharded — the optimized HLO's parameter shapes are the
  per-device LOCAL shapes, and the executable's input shardings carry the
  data-axis spec;
* the Gram reduction is a cross-device collective — ``all-reduce`` appears
  in the optimized HLO.

Capability parity: SURVEY §2.7 treeReduce/broadcast rows — mlmatrix's
explicit tree all-reduce becomes an XLA-inserted collective; these tests
prove it is actually inserted.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.linalg.bcd import _bcd_scan
from keystone_tpu.linalg.normal_equations import _ne_solve
from keystone_tpu.nodes.learning.weighted import _chunk_grams
from keystone_tpu.parallel.mesh import (
    DATA_AXIS,
    make_mesh,
    shard_batch,
    use_mesh,
)

N_DEV = 8


def _local_shape_pattern(n, *rest):
    dims = ",".join(str(d) for d in (n // N_DEV,) + rest)
    return f"f32[{dims}]"


@pytest.fixture
def data_mesh():
    return make_mesh()  # all 8 devices on the data axis


def test_scan_bcd_compiled_is_distributed(data_mesh):
    n, d, k, bs = 64, 16, 4, 8
    rng = np.random.default_rng(0)
    with use_mesh(data_mesh):
        A = shard_batch(rng.standard_normal((n, d)).astype(np.float32))
        y = shard_batch(rng.standard_normal((n, k)).astype(np.float32))
        compiled = _bcd_scan.lower(
            A, y, jnp.float32(1.0), None, block_size=bs, num_iter=1
        ).compile()
    txt = compiled.as_text()
    # Gram/cross reductions over the row-sharded operands must be collectives
    assert "all-reduce" in txt, "no cross-device reduction in compiled BCD"
    # operands arrive 1/N: local parameter shape present, global absent
    assert _local_shape_pattern(n, d) in txt
    assert f"f32[{n},{d}]{{1,0}} parameter" not in txt
    in_shardings = compiled.input_shardings[0]
    assert any(
        getattr(s, "spec", None) is not None and s.spec[0] == DATA_AXIS
        for s in in_shardings
    ), f"inputs not data-sharded: {in_shardings}"


def test_exact_solver_compiled_is_distributed(data_mesh):
    n, d, k = 64, 16, 4
    rng = np.random.default_rng(1)
    with use_mesh(data_mesh):
        A = shard_batch(rng.standard_normal((n, d)).astype(np.float32))
        b = shard_batch(rng.standard_normal((n, k)).astype(np.float32))
        compiled = _ne_solve.lower(A, b, jnp.float32(1.0)).compile()
    txt = compiled.as_text()
    assert "all-reduce" in txt
    assert _local_shape_pattern(n, d) in txt


def test_weighted_class_grams_compiled_is_distributed(data_mesh):
    """The masked per-class Gram einsum of the weighted solver reduces over
    the sharded row axis — must lower to a collective, with the descriptor
    operand arriving 1/N."""
    n, d, C = 64, 12, 4
    rng = np.random.default_rng(2)
    with use_mesh(data_mesh):
        A = shard_batch(rng.standard_normal((n, d)).astype(np.float32))
        mask = shard_batch(
            (rng.random((n, C)) < 0.3).astype(np.float32)
        )
        compiled = _chunk_grams.lower(A, mask).compile()
    txt = compiled.as_text()
    assert "all-reduce" in txt
    assert _local_shape_pattern(n, d) in txt


def test_replicated_inputs_compile_without_collectives(data_mesh):
    """Control for the assertions above: the SAME program lowered with
    replicated (unsharded) inputs must NOT contain a cross-device
    reduction — proving 'all-reduce' in the sharded lowerings comes from
    the 1/N distribution, not from something incidental."""
    n, d, k, bs = 64, 16, 4, 8
    rng = np.random.default_rng(3)
    A = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((n, k)).astype(np.float32))
    with use_mesh(data_mesh):
        compiled = _bcd_scan.lower(
            A, y, jnp.float32(1.0), None, block_size=bs, num_iter=1
        ).compile()
    assert "all-reduce" not in compiled.as_text()


def test_block_solver_model_axis_sharding():
    """VERDICT r4 #6: the MAIN block solver's d dimension distributes over
    MODEL_AXIS — W comes out P(model)-sharded (each device owns a column
    slice of the model), the data-axis Gram reduction is still a
    collective, and the result agrees with the unsharded solve."""
    from jax.sharding import PartitionSpec as P

    from keystone_tpu.linalg import solve_blockwise_l2_scan
    from keystone_tpu.parallel.mesh import MODEL_AXIS

    n, d, k, bs = 64, 16, 4, 4
    rng = np.random.default_rng(5)
    An = rng.standard_normal((n, d)).astype(np.float32)
    yn = rng.standard_normal((n, k)).astype(np.float32)
    means = An.mean(axis=0)

    W_rep = np.asarray(
        solve_blockwise_l2_scan(
            jnp.asarray(An), jnp.asarray(yn), reg=1.0, block_size=bs,
            num_iter=1, means=jnp.asarray(means),
        )
    )
    mesh = make_mesh(n_data=4, n_model=2)
    with use_mesh(mesh):
        W = solve_blockwise_l2_scan(
            jnp.asarray(An), jnp.asarray(yn), reg=1.0, block_size=bs,
            num_iter=1, means=jnp.asarray(means),
        )
        assert W.sharding.spec == P(MODEL_AXIS), W.sharding
        # per-device shard really is a 1/n_model column slice of the model
        shard_shapes = {s.data.shape for s in W.addressable_shards}
        assert shard_shapes == {(d // 2, k)}, shard_shapes

        from keystone_tpu.linalg.bcd import _bcd_scan_model_sharded

        jitted = _bcd_scan_model_sharded(n, d, bs, 1, True)
        txt = jitted.lower(
            jnp.asarray(An), jnp.asarray(yn), jnp.float32(1.0),
            jnp.asarray(means),
        ).compile().as_text()
        assert "all-reduce" in txt, "no cross-device Gram reduction"
    np.testing.assert_allclose(np.asarray(W), W_rep, rtol=2e-4, atol=2e-5)


def test_kept_factor_stack_shards_by_blocks_over_the_model_axis():
    """A multi-epoch fit keeps its factors (PR 26); on a data×model mesh the
    stack lies by blocks over MODEL_AXIS as W does — the axis exists to buy
    memory — and no block step gathers it whole."""
    from keystone_tpu.linalg import solve_blockwise_l2_scan
    from keystone_tpu.linalg.bcd import _bcd_scan_model_sharded

    n, d, k, bs, n_model = 64, 32, 3, 4, 2
    nblocks = d // bs
    rng = np.random.default_rng(8)
    An = rng.standard_normal((n, d)).astype(np.float32)
    yn = rng.standard_normal((n, k)).astype(np.float32)
    means = An.mean(axis=0)

    def solve():
        return solve_blockwise_l2_scan(
            jnp.asarray(An), jnp.asarray(yn), reg=1.0, block_size=bs,
            num_iter=3, means=jnp.asarray(means),
        )

    W_rep = np.asarray(solve())
    with use_mesh(make_mesh(n_data=4, n_model=n_model)):
        W = np.asarray(solve())
        txt = _bcd_scan_model_sharded(n, d, bs, 3, True).lower(
            jnp.asarray(An), jnp.asarray(yn), jnp.float32(1.0),
            jnp.asarray(means),
        ).compile().as_text()
    np.testing.assert_allclose(W, W_rep, rtol=2e-4, atol=2e-5)
    assert f"f32[{nblocks // n_model},{bs},{bs}]" in txt, "no local stack"
    assert f"f32[{nblocks},{bs},{bs}]" not in txt, "the whole stack somewhere"


def test_block_estimator_uses_model_axis_on_mixed_mesh():
    """BlockLeastSquaresEstimator.fit on a data×model mesh produces the
    same model as on a pure data mesh (the sharded compile is routed
    through transparently)."""
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

    n, d, k = 64, 16, 4
    rng = np.random.default_rng(6)
    An = rng.standard_normal((n, d)).astype(np.float32)
    yn = rng.standard_normal((n, k)).astype(np.float32)
    est = BlockLeastSquaresEstimator(block_size=4, num_iter=1, lam=0.5)
    m_data = est.fit(Dataset.of(jnp.asarray(An)), Dataset.of(jnp.asarray(yn)))
    with use_mesh(make_mesh(n_data=4, n_model=2)):
        m_mixed = est.fit(
            Dataset.of(jnp.asarray(An)), Dataset.of(jnp.asarray(yn))
        )
    Xt = rng.standard_normal((7, d)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(m_mixed.trace_batch(jnp.asarray(Xt))),
        np.asarray(m_data.trace_batch(jnp.asarray(Xt))),
        rtol=2e-4, atol=2e-4,
    )


def test_sharded_and_replicated_results_agree(data_mesh):
    n, d, k, bs = 64, 16, 4, 8
    rng = np.random.default_rng(4)
    An = rng.standard_normal((n, d)).astype(np.float32)
    yn = rng.standard_normal((n, k)).astype(np.float32)
    with use_mesh(data_mesh):
        W_sharded = np.asarray(
            _bcd_scan(
                shard_batch(An), shard_batch(yn), jnp.float32(1.0), None,
                block_size=bs, num_iter=1,
            )
        )
    W_rep = np.asarray(
        _bcd_scan(
            jnp.asarray(An), jnp.asarray(yn), jnp.float32(1.0), None,
            block_size=bs, num_iter=1,
        )
    )
    np.testing.assert_allclose(W_sharded, W_rep, rtol=2e-4, atol=2e-5)
