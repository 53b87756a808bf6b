"""models/jamba.py and ops/selective_scan.py at tiny sizes on the CPU: the
two forms every mixer has agree with each other, a padded rung leaves what
the unpadded sequence leaves, and the Pallas kernel (interpret mode)
computes what the ``lax.scan`` computes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import jamba as J
from paddle_tpu.ops import selective_scan as SS
from paddle_tpu.ops.decode_attention import (decode_attention,
                                             prefill_attention)

CFG = J.JAMBA_TINY                   # float32: 4 layers, attention at 2
# float32 sums in another order (a scan against a step, a padded product
# against an unpadded one): a few units in the last place of values of
# order 1, three hundred times under what bfloat16 rounding would show
F32_TOL = dict(rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def params():
    return J.init_params(jax.random.PRNGKey(3), CFG)


def _mamba_layer(params, m):
    return jax.tree_util.tree_map(lambda a: a[m], params["mamba"])


def _scan_inputs(seed, T, Di=256, N=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(k[0], (T, Di), jnp.float32),
        delta=jax.nn.softplus(jax.random.normal(k[1], (T, Di)) - 3.0),
        A_t=-jnp.exp(0.5 * jax.random.normal(k[2], (N, Di))),
        Bm=jax.random.normal(k[3], (T, N)),
        Cm=jax.random.normal(k[4], (T, N)),
        D=jnp.ones((Di,)), z=jax.random.normal(k[5], (T, Di)))


def test_layer_pattern_is_data():
    assert CFG.attention_layers == (2,) and CFG.num_mamba_layers == 3
    assert CFG.segments() == [("mamba", 0, 2), ("attention", 0, 1),
                              ("mamba", 2, 1)]
    full = J.JambaConfig()
    assert full.attention_layers == (7, 21)
    assert full.segments() == [("mamba", 0, 7), ("attention", 0, 1),
                               ("mamba", 7, 13), ("attention", 1, 1),
                               ("mamba", 20, 6)]
    shapes = jax.tree_util.tree_leaves(
        J.leaf_shapes(full), is_leaf=lambda s: isinstance(s, tuple))
    assert sum(int(np.prod(s)) for s in shapes) == 3_029_337_472


@pytest.mark.parametrize("length", [32, 19, 1])
def test_selective_scan_kernel_in_interpret_mode_matches_the_lax_scan(
        length):
    a = _scan_inputs(0, 32)
    want = SS.selective_scan_reference(length=length, **a)
    got = SS.selective_scan(length=length, chunk=8, use_pallas=True, **a)
    np.testing.assert_allclose(got[0][:length], want[0][:length], **F32_TOL)
    np.testing.assert_allclose(got[1], want[1], **F32_TOL)
    # off the TPU the same entry runs the lax.scan
    plain = SS.selective_scan(length=length, **a)
    np.testing.assert_array_equal(plain[1], want[1])


def test_padding_past_length_leaves_the_state_untouched():
    a = _scan_inputs(1, 24)
    short = {k: (v[:15] if v.shape[0] == 24 else v) for k, v in a.items()}
    _, h_padded = SS.selective_scan(length=15, **a)
    _, h_exact = SS.selective_scan(length=15, **short)
    np.testing.assert_allclose(h_padded, h_exact, **F32_TOL)


def test_state_update_steps_to_the_scans_state_and_spares_who_sits_out():
    a = _scan_inputs(2, 9)
    y_seq, h_seq = SS.selective_scan(length=9, **a)
    h = jnp.zeros((2,) + a["A_t"].shape)
    ys = []
    for t in range(9):
        y, h = SS.selective_state_update(
            h, *(jnp.stack([a[k][t]] * 2) for k in ("x", "delta")),
            a["A_t"], *(jnp.stack([a[k][t]] * 2) for k in ("Bm", "Cm")),
            a["D"], jnp.stack([a["z"][t]] * 2),
            active=jnp.asarray([1, t < 4], jnp.int32))
        ys.append(y[0])
    np.testing.assert_allclose(jnp.stack(ys), y_seq, **F32_TOL)
    np.testing.assert_allclose(h[0], h_seq, **F32_TOL)
    # lane 1 rode the first four tokens only: bit for bit what a scan of
    # four leaves, whatever the later calls fed it
    _, h4 = SS.selective_scan(length=4, **a)
    np.testing.assert_allclose(h[1], h4, **F32_TOL)
    h_before = h
    _, h_after = SS.selective_state_update(
        h, a["x"][:2], a["delta"][:2], a["A_t"], a["Bm"][:2], a["Cm"][:2],
        a["D"], a["z"][:2], active=jnp.zeros((2,), jnp.int32))
    np.testing.assert_array_equal(h_after, h_before)


def test_mamba_mixer_sequence_form_against_its_one_token_form(params):
    p = _mamba_layer(params, 1)
    T = 12
    u = jax.random.normal(jax.random.PRNGKey(5), (T, CFG.hidden_size))
    out, conv, h = J.mamba_sequence(u, p, jnp.int32(T), CFG)
    conv_s = jnp.zeros((1, (CFG.mamba_d_conv - 1) * CFG.d_inner))
    h_s = jnp.zeros((1, CFG.mamba_d_state, CFG.d_inner))
    outs = []
    for t in range(T):
        o, conv_s, h_s = J.mamba_step(u[t][None], p, conv_s, h_s,
                                      jnp.ones((1,), jnp.int32), CFG)
        outs.append(o[0])
    np.testing.assert_allclose(jnp.stack(outs), out, **F32_TOL)
    np.testing.assert_allclose(conv_s[0], conv, **F32_TOL)
    np.testing.assert_allclose(h_s[0], h, **F32_TOL)


@pytest.mark.parametrize("length", [11, 2])
def test_padded_rung_leaves_the_state_of_position_length_minus_one(
        params, length):
    """A rung of 16 with ``length`` valid tokens against the unpadded
    sequence: the outputs before ``length`` and both states (a length
    under ``d_conv - 1`` keeps zeros at the head of the conv state)."""
    p = _mamba_layer(params, 0)
    u = jax.random.normal(jax.random.PRNGKey(6), (16, CFG.hidden_size))
    out_p, conv_p, h_p = J.mamba_sequence(u, p, jnp.int32(length), CFG)
    out_e, conv_e, h_e = J.mamba_sequence(u[:length], p, jnp.int32(length),
                                          CFG)
    np.testing.assert_allclose(out_p[:length], out_e, **F32_TOL)
    np.testing.assert_allclose(conv_p, conv_e, **F32_TOL)
    np.testing.assert_allclose(h_p, h_e, **F32_TOL)
    assert float(jnp.abs(h_p).max()) > 0


def test_attention_mixer_grouped_heads_sequence_against_one_token():
    """20-over-1 in small: 4 query heads share 1 key/value head; the
    prefill form over T rows against the tick form over a cache."""
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    T, nh, kvh, hd = 9, 4, 1, 16
    q = jax.random.normal(k[0], (1, T, nh, hd))
    kk = jax.random.normal(k[1], (1, T, kvh, hd))
    v = jax.random.normal(k[2], (1, T, kvh, hd))
    seq = prefill_attention(q, kk, v)
    wide = prefill_attention(q, jnp.repeat(kk, nh, axis=2),
                             jnp.repeat(v, nh, axis=2))
    np.testing.assert_allclose(seq, wide, **F32_TOL)
    cache_k = jnp.zeros((1, 16, kvh, hd)).at[:, :T].set(kk)
    cache_v = jnp.zeros((1, 16, kvh, hd)).at[:, :T].set(v)
    for t in (0, 4, T - 1):
        one = decode_attention(q[:, t], cache_k, cache_v,
                               jnp.asarray([t + 1], jnp.int32))
        np.testing.assert_allclose(one, seq[:, t], **F32_TOL)
    empty = decode_attention(q[:, 0], cache_k, cache_v,
                             jnp.zeros((1,), jnp.int32))
    np.testing.assert_array_equal(empty, jnp.zeros_like(empty))


def test_forward_is_causal_and_the_head_is_tied(params):
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, 14),
                       jnp.int32)
    full = J.forward(params, toks, CFG)
    head = J.forward(params, toks[:9], CFG)
    np.testing.assert_allclose(full[:9], head, **F32_TOL)
    assert full.shape == (14, CFG.vocab_size) and full.dtype == jnp.float32
    assert "lm_head" not in params and "wpe" not in params
