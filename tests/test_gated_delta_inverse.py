"""``ops/gated_delta._unit_lower_inverse``: the chunk kernels' blocked inverse
of ``I + A`` called on plain arrays on the CPU, against a float64 triangular
solve, at every block layout the rule gives (one block of C up to 32, two
blocks of 32 at the cells' 64, four and a doubling round at 128); and both
chunked entries under the Pallas form (interpret mode) at a chunk of 64,
where the two-block path runs, against their XLA forms and the recurrence.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import gated_delta as GD

EPS = float(np.finfo(np.float32).eps)
# |T - T64| <= INVERSE_TOL * eps * C * max|T64|: the draws below read 0.0017
# to 0.0112 of eps * C (the row-by-row substitution this form replaced:
# 0.0019 to 0.0112 on the same draws), and max|T64| is the diagonal's 1
INVERSE_TOL = 0.05


def _chunk_system(C, seed, dk=96, live=None):
    """``A`` of one chunk at the cells' scales, in float64: unit keys,
    ``beta`` over (0, 2), gates from a thousandth to 1.6 nats a token
    (``log alpha = -exp(u)``, ``u`` from -7); positions from ``live`` on
    are padding (``beta = 0``, ``log alpha = 0``: rows of exact zeros)."""
    rng = np.random.default_rng(seed)
    k = rng.standard_normal((C, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = 2 / (1 + np.exp(-2 * rng.standard_normal(C)))
    alpha_log = -np.exp(rng.uniform(-7.0, 0.5, C))
    if live is not None:
        beta[live:] = 0.0
        alpha_log[live:] = 0.0
    g = np.cumsum(alpha_log)
    return np.tril(
        beta[:, None] * np.exp(g[:, None] - g[None, :]) * (k @ k.T), -1)


def _inverse64(A):
    with jax.enable_x64(True):
        C = A.shape[0]
        return np.asarray(jax.scipy.linalg.solve_triangular(
            jnp.eye(C, dtype=jnp.float64) + jnp.asarray(A, jnp.float64),
            jnp.eye(C, dtype=jnp.float64), lower=True, unit_diagonal=True))


def _inverse(A):
    at = jnp.asarray(A.T, jnp.float32)
    T = jax.jit(GD._unit_lower_inverse)(at)
    assert T.dtype == jnp.float32 and T.shape == A.shape
    return np.asarray(T, np.float64)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("C", [8, 16, 32, 64, 128])
def test_blocked_inverse_is_the_float64_solve(C, seed):
    A = _chunk_system(C, seed)
    want = _inverse64(A)
    got = _inverse(A)
    assert np.abs(got - want).max() \
        <= INVERSE_TOL * EPS * C * np.abs(want).max()
    # what it is for: (I + A) T = I
    assert np.abs((np.eye(C) + A) @ got - np.eye(C)).max() \
        <= INVERSE_TOL * EPS * C * np.abs(want).max()


@pytest.mark.parametrize("C,live", [(64, 17), (64, 40), (128, 70), (16, 5)])
def test_a_padded_tail_keeps_its_rows_of_the_identity(C, live):
    """Rows of exact zeros (a chunk's tail past ``length``): the tail's
    rows of T are the identity's to the bit, whichever block they lie in,
    and the live part is the inverse of its own corner."""
    A = _chunk_system(C, 3, live=live)
    assert not A[live:].any()
    got = _inverse(A)
    np.testing.assert_array_equal(got[live:], np.eye(C)[live:])
    want = _inverse64(A[:live, :live])
    assert np.abs(got[:live, :live] - want).max() \
        <= INVERSE_TOL * EPS * C * np.abs(want).max()
    assert not got[:live, live:].any()


def test_the_block_follows_from_the_chunk_alone():
    """One body, no argument beside the matrix: 32 where it divides a
    larger chunk, else the chunk."""
    assert list(inspect.signature(GD._unit_lower_inverse).parameters) \
        == ["at"]
    assert GD._INVERSE_BLOCK == 32
    # a chunk that 32 does not divide takes one block and is still right
    A = _chunk_system(40, 5)
    want = _inverse64(A)
    assert np.abs(_inverse(A) - want).max() \
        <= INVERSE_TOL * EPS * 40 * np.abs(want).max()


def _inputs(T, channel, H=3, dk=16, dv=32, seed=0):
    """As ``tests/test_olmo_hybrid.py:_delta_inputs`` and
    ``tests/test_solar_open2.py:_kda_inputs`` (a fast channel among
    them)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, H, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dv))
    shape = (T, H, dk) if channel else (T, H)
    alpha_log = -jnp.exp(jax.random.uniform(ks[3], shape, minval=-7.0,
                                            maxval=0.5))
    if channel:
        alpha_log = alpha_log.at[:, 0, 1].set(-3.0)
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (T, H)))
    return q, k, v, alpha_log, beta


@pytest.mark.parametrize("length", [128, 81])
@pytest.mark.parametrize("entry", ["gated_delta_chunked", "kda_chunked"])
def test_chunked_kernels_at_the_cells_chunk(entry, length):
    """Two chunks of 64 (two diagonal blocks of 32 each), whole and with a
    padded tail that ends inside the second chunk's first block: the
    Pallas form against the XLA form and the recurrence, to the tolerances
    ``tests/test_olmo_hybrid.py`` and ``tests/test_solar_open2.py``
    hold."""
    fn = getattr(GD, entry)
    q, k, v, alpha_log, beta = _inputs(128, entry == "kda_chunked")
    L = jnp.int32(length)
    o, St = fn(q, k, v, alpha_log, beta, L, chunk=64, use_pallas=True)
    xo, xS = fn(q, k, v, alpha_log, beta, L, chunk=64, use_pallas=False)
    ro, rS = GD.gated_delta_recurrence(q, k, v, alpha_log, beta, length)
    assert np.isfinite(np.asarray(o)).all()
    for want_o, want_S in ((xo, xS), (ro, rS)):
        np.testing.assert_allclose(o[:length], want_o[:length], rtol=0,
                                   atol=2e-6)
        np.testing.assert_allclose(St, want_S, rtol=0, atol=5e-6)
