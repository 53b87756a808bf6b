"""Real-chip lane: the PR 16 kernels whose blocks PR 22 re-laid for the
chip's compiler (fused_ln backward partials, the paged decode
kernel), compiled by Mosaic at gpt_wide widths and checked against the
unfused XLA expressions on the same chip. Interpret-mode parity lives in
tests/test_pallas_fused.py; the described-chip compiles in
tests/test_chip_compile.py prove they compile, this proves what they
compute.

    chiprun -- env PADDLE_TPU_NATIVE=1 python -m pytest tests/tpu -q
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="TPU lane: requires a live TPU backend "
           "(run with PADDLE_TPU_NATIVE=1 on the chip host)")

from paddle_tpu.ops import decode_attention as DA
from paddle_tpu.ops import pallas_kernels as PK

from tests.tpu._lane import record as _record

B, S, NH, HD, D, PAGE = 8, 1024, 16, 128, 2048, 16


def _ref_ln(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias
            ).astype(x.dtype)


def test_fused_ln_fwd_bwd_matches_xla_on_tpu():
    rng = np.random.default_rng(0)
    rows = 2048
    x = jnp.asarray(rng.standard_normal((rows, D)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((rows, D)), jnp.float32)
    scale = jnp.asarray(1.0 + 0.1 * rng.standard_normal(D), jnp.float32)
    bias = jnp.asarray(0.1 * rng.standard_normal(D), jnp.float32)
    badd = jnp.asarray(0.1 * rng.standard_normal(D), jnp.float32)
    w = jnp.asarray(rng.standard_normal((rows, D)), jnp.float32)

    def fused(x, scale, bias, res, badd):
        y, s = PK.fused_ln(x, scale, bias, residual=res, bias_add=badd,
                           return_residual=True)
        return jnp.sum(y * w) + jnp.sum(s)

    def ref(x, scale, bias, res, badd):
        s = (res + x) + badd
        return jnp.sum(_ref_ln(s, scale, bias) * w) + jnp.sum(s)

    args = (x, scale, bias, res, badd)
    lowered = jax.jit(jax.value_and_grad(fused, argnums=(0, 1, 2, 3, 4))
                      ).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    vf, gf = lowered.compile()(*args)
    vr, gr = jax.jit(jax.value_and_grad(ref, argnums=(0, 1, 2, 3, 4)))(*args)
    np.testing.assert_allclose(float(vf), float(vr), rtol=1e-5)
    worst = 0.0
    for a, b, name in zip(gf, gr, ("x", "scale", "bias", "res", "badd")):
        a, b = np.asarray(a), np.asarray(b)
        # dscale/dbias sum 2048 rows: tolerance relative to the magnitude
        tol = 1e-4 * max(1.0, float(np.abs(b).max()))
        np.testing.assert_allclose(a, b, atol=tol, rtol=1e-4, err_msg=name)
        worst = max(worst, float(np.abs(a - b).max()))
    _record("fused_ln_fwd_bwd_max_abs_err", worst)


@pytest.mark.parametrize("layers", [None, 3], ids=["one_layer", "pool5d"])
@pytest.mark.parametrize("cdt", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_fused_decode_paged_matches_xla_on_tpu(cdt, layers):
    """The paged decode step against the unfused XLA expressions, over one
    layer's [P, page, nh * hd] pool and over the engine's whole
    [L, P, page, nh * hd] pool with a traced layer index (the tick's
    carried pools). Ragged lengths; slot 6 is a dead lane (all-zero
    table, position 0: it writes the scratch page)."""
    rng = np.random.default_rng(2)
    M = S // PAGE
    P = 1 + B * M                            # page 0 = scratch
    lead = () if layers is None else (layers,)
    kp = jnp.asarray(rng.standard_normal(lead + (P, PAGE, NH * HD)), cdt)
    vp = jnp.asarray(rng.standard_normal(lead + (P, PAGE, NH * HD)), cdt)
    q, nk, nv = (jnp.asarray(rng.standard_normal((B, NH, HD)), cdt)
                 for _ in range(3))
    perm = rng.permutation(np.arange(1, P)).reshape(B, M)   # disjoint
    perm[6] = 0
    tables = jnp.asarray(perm, jnp.int32)
    positions = jnp.asarray([0, 5, 255, 256, 700, 1023, 0, 512], jnp.int32)
    layer = None if layers is None else jnp.int32(1)
    live = np.arange(B) != 6

    @jax.jit
    def ref(q, kp, vp, nk, nv, layer):
        phys = tables[jnp.arange(B), positions // PAGE]
        rows = positions % PAGE
        kp2 = DA.paged_cache_update(kp, nk.reshape(B, -1), phys, rows,
                                    layer=layer)
        vp2 = DA.paged_cache_update(vp, nv.reshape(B, -1), phys, rows,
                                    layer=layer)
        gk = DA.paged_gather(kp2, tables, layer=layer, heads=(NH, HD))
        gv = DA.paged_gather(vp2, tables, layer=layer, heads=(NH, HD))
        return DA.decode_attention(q, gk, gv, positions + 1), kp2, vp2

    fused = jax.jit(lambda q, kp, vp, nk, nv, layer:
                    PK.fused_paged_decode_attention(
                        q, kp, vp, nk, nv, tables, positions, layer=layer))
    assert "tpu_custom_call" in fused.lower(q, kp, vp, nk, nv,
                                            layer).as_text()
    out, kp2, vp2 = fused(q, kp, vp, nk, nv, layer)
    r_out, r_kp, r_vp = ref(q, kp, vp, nk, nv, layer)
    np.testing.assert_array_equal(np.asarray(kp2, np.float32),
                                  np.asarray(r_kp, np.float32))
    np.testing.assert_array_equal(np.asarray(vp2, np.float32),
                                  np.asarray(r_vp, np.float32))
    tol = 2e-2 if cdt == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(r_out, np.float32)[live],
                               atol=tol, rtol=tol)
