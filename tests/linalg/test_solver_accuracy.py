"""Float64 agreement oracles for the solvers (VERDICT r3 #2).

Parity spec: the reference solves in float64 Breeze/LAPACK; its suites pin
distributed-vs-local agreement (BlockLinearMapperSuite.scala:19-56,
PCASuite.scala:85). Here the independent oracle is NumPy float64 running the
SAME algorithm (same block order, same updates), so any precision loss in
the TPU path — not algorithmic difference — is what the comparison measures.

The shapes are small enough for CPU but large enough (reduction depth in the
tens of thousands) that single-pass bf16 matmuls measurably fail: the last
test *injects* a bf16 Gram and asserts the agreement bar catches it, proving
the 1e-3 tolerance is a live signal, not a formality.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.linalg import solve_blockwise_l2, solve_least_squares
from keystone_tpu.linalg.bcd import solve_blockwise_l2_scan

RTOL = 1e-3  # the agreement bar from VERDICT r3 next-round item 2


def _bcd_f64(A, y, reg, block_size, num_iter):
    """NumPy float64 BCD — same update order as linalg/bcd.py."""
    A = np.asarray(A, dtype=np.float64)
    y = np.asarray(y, dtype=np.float64)
    n, d = A.shape
    k = y.shape[1]
    nblocks = d // block_size
    W = [np.zeros((block_size, k)) for _ in range(nblocks)]
    pred = np.zeros_like(y)
    for _ in range(num_iter):
        for j in range(nblocks):
            Aj = A[:, j * block_size : (j + 1) * block_size]
            r = y - pred + Aj @ W[j]
            G = Aj.T @ Aj + reg * np.eye(block_size)
            Wj = np.linalg.solve(G, Aj.T @ r)
            pred = pred + Aj @ (Wj - W[j])
            W[j] = Wj
    return np.concatenate(W, axis=0)


def _problem(n=16384, d=2048, k=16, seed=0, noise=0.1):
    """Ridge problem with a realistic (~30) condition number: feature columns
    span 1.5 decades of scale, like un-normalized featurizer outputs. A
    spherical iid Gaussian would damp precision loss in the solve and let a
    bf16 Gram slip under the bar — conditioning is what makes the tolerance
    a live signal."""
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((n, d)).astype(np.float32)
    A *= np.logspace(-0.75, 0.75, d, dtype=np.float32)
    w_star = rng.standard_normal((d, k)).astype(np.float32) / np.sqrt(d)
    y = (A @ w_star + noise * rng.standard_normal((n, k))).astype(np.float32)
    return A, y, w_star


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_exact_solver_agrees_with_float64():
    A, y, _ = _problem()
    reg = 1e-2
    W = np.asarray(solve_least_squares(jnp.asarray(A), jnp.asarray(y), reg=reg))
    A64 = A.astype(np.float64)
    W64 = np.linalg.solve(
        A64.T @ A64 + reg * np.eye(A.shape[1]), A64.T @ y.astype(np.float64)
    )
    assert _rel(W, W64) < RTOL


@pytest.mark.parametrize("num_iter", [1, 2])
def test_scan_bcd_agrees_with_float64(num_iter):
    A, y, _ = _problem(n=8192, d=2048, k=8)
    reg, bs = 10.0, 512
    W = np.asarray(
        solve_blockwise_l2_scan(
            jnp.asarray(A), jnp.asarray(y), reg=reg, block_size=bs,
            num_iter=num_iter,
        )
    )
    W64 = _bcd_f64(A, y, reg, bs, num_iter)
    assert _rel(W, W64) < RTOL


def test_hostloop_bcd_agrees_with_float64():
    A, y, _ = _problem(n=8192, d=2048, k=8)
    reg, bs = 10.0, 512
    blocks = [jnp.asarray(A[:, i : i + bs]) for i in range(0, A.shape[1], bs)]
    Ws = solve_blockwise_l2(blocks, jnp.asarray(y), reg=reg, num_iter=1)
    W = np.concatenate([np.asarray(w) for w in Ws], axis=0)
    W64 = _bcd_f64(A, y, reg, bs, 1)
    assert _rel(W, W64) < RTOL


def test_scan_and_hostloop_paths_agree():
    """The two BCD paths are the same algorithm; they must agree to much
    tighter than the f64 bar (they share precision and order)."""
    A, y, _ = _problem(n=4096, d=1024, k=4)
    reg, bs = 5.0, 256
    blocks = [jnp.asarray(A[:, i : i + bs]) for i in range(0, A.shape[1], bs)]
    Ws = solve_blockwise_l2(blocks, jnp.asarray(y), reg=reg, num_iter=2)
    W_loop = np.concatenate([np.asarray(w) for w in Ws], axis=0)
    W_scan = np.asarray(
        solve_blockwise_l2_scan(
            jnp.asarray(A), jnp.asarray(y), reg=reg, block_size=bs, num_iter=2
        )
    )
    np.testing.assert_allclose(W_scan, W_loop, rtol=2e-4, atol=2e-5)


def test_scan_bcd_centering_matches_explicit():
    """means= fused centering ≡ solving the explicitly centered matrix."""
    A, y, _ = _problem(n=4096, d=1024, k=4, seed=3)
    A = A + 2.5  # give the columns real means
    reg, bs = 5.0, 256
    mean = A.mean(axis=0)
    W_fused = np.asarray(
        solve_blockwise_l2_scan(
            jnp.asarray(A), jnp.asarray(y), reg=reg, block_size=bs,
            num_iter=1, means=jnp.asarray(mean),
        )
    )
    W_explicit = np.asarray(
        solve_blockwise_l2_scan(
            jnp.asarray(A - mean), jnp.asarray(y), reg=reg, block_size=bs,
            num_iter=1,
        )
    )
    np.testing.assert_allclose(W_fused, W_explicit, rtol=2e-4, atol=2e-5)


def test_streaming_solver_agrees_with_float64():
    """Chunked Gram accumulation ≡ the one-shot float64 solve: the streaming
    path is how >HBM datasets solve exactly, so it gets the same bar."""
    from keystone_tpu.linalg import solve_least_squares_streaming

    A, y, _ = _problem(n=16384, d=1024, k=8, seed=1)
    reg = 1e-2
    chunk = 4096
    chunks = (
        (A[i : i + chunk], y[i : i + chunk]) for i in range(0, len(A), chunk)
    )
    W = np.asarray(solve_least_squares_streaming(chunks, reg=reg))
    A64 = A.astype(np.float64)
    W64 = np.linalg.solve(
        A64.T @ A64 + reg * np.eye(A.shape[1]), A64.T @ y.astype(np.float64)
    )
    assert _rel(W, W64) < RTOL


def test_injected_bf16_gram_fails_the_bar():
    """Teeth check: recompute the exact solve with a single-pass-bf16 Gram
    (the regression the agreement bar exists to catch) and assert it FAILS.
    If this test ever breaks, the bar has gone soft."""
    A, y, _ = _problem()
    reg = 1e-2

    @jax.jit
    def bf16_solve(A, y):
        Ab = A.astype(jnp.bfloat16)
        G = (Ab.T @ Ab).astype(jnp.float32)
        c = (Ab.T @ y.astype(jnp.bfloat16)).astype(jnp.float32)
        G = G + reg * jnp.eye(G.shape[0], dtype=jnp.float32)
        cho = jax.scipy.linalg.cho_factor(G, lower=True)
        return jax.scipy.linalg.cho_solve(cho, c)

    W_bf16 = np.asarray(bf16_solve(jnp.asarray(A), jnp.asarray(y)))
    A64 = A.astype(np.float64)
    W64 = np.linalg.solve(
        A64.T @ A64 + reg * np.eye(A.shape[1]), A64.T @ y.astype(np.float64)
    )
    assert _rel(W_bf16, W64) > RTOL


# ---------------------------------------------------------------------------
# the scan solver factors each block once a fit (PR 26)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("reg", [0.0, 0.1])
@pytest.mark.parametrize("warm", [False, True], ids=["cold", "init"])
@pytest.mark.parametrize("centered", [False, True], ids=["raw", "means"])
@pytest.mark.parametrize("num_iter", [2, 5])
def test_kept_factors_equal_the_recomputing_block_path(
    num_iter, centered, warm, reg
):
    """``_bcd_scan`` solves every epoch against factors made once; the
    per-block path still forms and factors each block's Gram in every
    epoch. Same numbers in the same order: they agree far inside the
    float64 bar."""
    from keystone_tpu.linalg.bcd import _bcd_scan

    n, d, k, bs = 512, 64, 3, 16
    rng = np.random.default_rng(7)
    A = rng.standard_normal((n, d)).astype(np.float32) + 0.5
    y = rng.standard_normal((n, k)).astype(np.float32)
    means = A.mean(axis=0) if centered else None
    init = (
        0.1 * rng.standard_normal((d, k)).astype(np.float32) if warm else None
    )
    blocks = [jnp.asarray(A[:, i : i + bs]) for i in range(0, d, bs)]
    Ws = solve_blockwise_l2(
        blocks, jnp.asarray(y), reg=reg, num_iter=num_iter,
        means=None if means is None else [
            jnp.asarray(means[i : i + bs]) for i in range(0, d, bs)
        ],
        init=None if init is None else [
            jnp.asarray(init[i : i + bs]) for i in range(0, d, bs)
        ],
    )
    W_loop = np.concatenate([np.asarray(w) for w in Ws], axis=0)
    args = (
        jnp.asarray(A), jnp.asarray(y), jnp.float32(reg),
        None if means is None else jnp.asarray(means),
    )
    if init is not None:
        args += (jnp.asarray(init),)
    W_scan = np.asarray(_bcd_scan(*args, block_size=bs, num_iter=num_iter))
    assert _rel(W_scan, W_loop) < 1e-5


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _scan_program(num_iter, nblocks=4, bs=8, n=32, k=3):
    from keystone_tpu.linalg.bcd import _bcd_scan_impl

    return jax.make_jaxpr(
        lambda A, y, reg, means: _bcd_scan_impl(
            A, y, reg, means, block_size=bs, num_iter=num_iter
        )
    )(
        jnp.ones((n, nblocks * bs)), jnp.ones((n, k)), jnp.float32(0.1),
        jnp.zeros((nblocks * bs,)),
    ).jaxpr


def _shapes(eqns):
    return {
        tuple(v.aval.shape)
        for eqn in eqns
        for v in list(eqn.invars) + list(eqn.outvars)
        if hasattr(v.aval, "shape")
    }


def test_five_epochs_factor_outside_the_epoch_scan():
    nblocks, bs = 4, 8
    program = _scan_program(num_iter=5, nblocks=nblocks, bs=bs)
    (epochs,) = [
        e for e in program.eqns
        if e.primitive.name == "scan" and e.params["length"] == 5
    ]
    body = list(_eqns(epochs.params["jaxpr"].jaxpr))
    assert not [e for e in body if e.primitive.name == "cholesky"]
    assert not [
        e for e in body
        if e.primitive.name == "dot_general"
        and tuple(e.outvars[0].aval.shape) == (bs, bs)
    ]
    # the epochs do solve, against the kept stack
    assert [e for e in body if e.primitive.name == "triangular_solve"]
    assert (nblocks, bs, bs) in _shapes(body)
    whole = list(_eqns(program))
    assert len([e for e in whole if e.primitive.name == "cholesky"]) == 1
    assert len([
        e for e in whole
        if e.primitive.name == "dot_general"
        and tuple(e.outvars[0].aval.shape) == (bs, bs)
    ]) == 1


def test_one_epoch_factors_in_place_and_keeps_no_stack():
    nblocks, bs = 4, 8
    whole = list(_eqns(_scan_program(num_iter=1, nblocks=nblocks, bs=bs)))
    assert len([e for e in whole if e.primitive.name == "cholesky"]) == 1
    assert (nblocks, bs, bs) not in _shapes(whole)


@pytest.mark.parametrize("num_iter,kept", [(1, False), (3, True)])
def test_scan_solver_work_states_what_the_program_keeps(num_iter, kept):
    from keystone_tpu.linalg.bcd import scan_solver_work

    nblocks, bs = 4, 8
    work = scan_solver_work(nblocks * bs, bs, num_iter)
    whole = list(_eqns(_scan_program(num_iter=num_iter, nblocks=nblocks, bs=bs)))
    assert ((nblocks, bs, bs) in _shapes(whole)) is kept
    assert work == {
        "gram_products": nblocks,
        "factor_bytes": nblocks * bs * bs * 4 if kept else 0,
    }
