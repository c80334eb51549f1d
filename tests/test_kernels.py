"""Pallas kernel validation (interpret mode) against pure-jnp oracles.

The wrappers compile for the TPU by default; every call here passes
``interpret=True`` explicitly, and a call without it off the TPU raises.

Each kernel is swept over shapes/dtypes (explicit grid + hypothesis-driven
random shapes) and asserted allclose to its ref.py oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rwkv6.ops import wkv6
from repro.kernels.rwkv6.ref import wkv6_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hkv,hd,window,softcap", [
    (2, 256, 4, 2, 64, 0, 0.0),        # GQA
    (1, 512, 8, 8, 32, 0, 0.0),        # MHA
    (2, 256, 4, 2, 64, 128, 0.0),      # sliding window
    (1, 256, 4, 1, 64, 0, 30.0),       # softcap (gemma-style), MQA
    (1, 128, 2, 2, 128, 64, 20.0),     # window + softcap
])
def test_flash_attention_matches_ref(B, S, H, Hkv, hd, window, softcap,
                                     dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), dtype)
    got = flash_attention(q, k, v, window=window, softcap=softcap,
                          block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(st.sampled_from([64, 128, 256]), st.sampled_from([1, 2]),
       st.sampled_from([16, 32, 64]), st.sampled_from([1, 2, 4]),
       st.booleans())
def test_flash_attention_property(S, B, hd, g, windowed):
    Hkv = 2
    H = Hkv * g
    ks = jax.random.split(jax.random.PRNGKey(S * 7 + hd), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
    window = S // 2 if windowed else 0
    got = flash_attention(q, k, v, window=window, block_q=32, block_k=32,
                          interpret=True)
    ref = attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(got, ref, rtol=3e-5, atol=3e-5)


def test_flash_matches_model_chunked_path():
    """The Pallas kernel and the XLA chunked fallback must agree."""
    from repro.models.attention import _sdpa_chunked

    class Cfg:
        logit_softcap = 0.0
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 256, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 256, 2, 32), jnp.float32)
    a = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    b = _sdpa_chunked(Cfg, q, k, v, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(a.reshape(2, 256, -1), b, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("call", ["flash_attention", "rmsnorm", "wkv6"])
def test_wrappers_refuse_to_run_compiled_off_the_tpu(call):
    """Interpret mode is the caller's choice, never inferred: without it a
    wrapper compiles for the TPU, and the CPU backend refuses that."""
    assert jax.default_backend() != "tpu"
    x = jnp.ones((1, 128, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        if call == "flash_attention":
            flash_attention(x, x, x, block_q=64, block_k=64)
        elif call == "rmsnorm":
            rmsnorm(x, jnp.ones((64,), jnp.float32))
        else:
            wkv6(x, x, x, x, jnp.ones((2, 64), jnp.float32),
                 jnp.zeros((1, 2, 64, 64), jnp.float32), chunk=64)


# ---------------------------------------------------------------- rwkv6
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,hd,chunk", [
    (2, 64, 2, 16, 16),
    (1, 128, 4, 32, 32),
    (2, 96, 1, 64, 32),    # chunk not dividing T -> falls back to smaller
])
def test_wkv6_matches_ref(B, T, H, hd, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    r = jax.random.normal(ks[0], (B, T, H, hd), dtype) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, hd), dtype) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, hd), dtype) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hd), dtype)) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (H, hd), dtype) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, hd, hd), jnp.float32) * 0.1

    y, sT = wkv6(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    flat = lambda x: jnp.swapaxes(x, 1, 2).reshape(B * H, T, hd)
    y_ref, sT_ref = wkv6_ref(flat(r), flat(k), flat(v), flat(w),
                             jnp.tile(u[None], (B, 1, 1)).reshape(B * H, hd),
                             s0.reshape(B * H, hd, hd))
    y_ref = jnp.swapaxes(y_ref.reshape(B, H, T, hd), 1, 2).reshape(B, T, H * hd)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(sT.reshape(B * H, hd, hd), sT_ref,
                               **_tol(dtype))


def test_wkv6_state_carry_composes():
    """Running two half-sequences with carried state == one full run."""
    B, T, H, hd = 1, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) * 0.5 for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hd))) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    y_full, s_full = wkv6(r, k, v, w, u, s0, chunk=16, interpret=True)
    y1, s_mid = wkv6(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u, s0,
                     chunk=16, interpret=True)
    y2, s_end = wkv6(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u, s_mid,
                     chunk=16, interpret=True)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], axis=1), y_full,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_end, s_full, rtol=1e-5, atol=1e-5)


def test_wkv6_matches_model_layer():
    """Kernel agrees with the model's scan implementation (rwkv.py)."""
    from repro.configs import get_config
    from repro.models import rwkv as R
    cfg = get_config("rwkv6_1_6b", reduced=True)
    p = R.init_rwkv(jax.random.PRNGKey(0), cfg)
    B, S, d = 2, 32, cfg.d_model
    hd = cfg.ssm.head_dim
    H = d // hd
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d)) * 0.1
    state = R.init_rwkv_state(cfg, B)
    y_model, _ = R.apply_rwkv_seq(cfg, p, x, state)

    # same projections, kernel recurrence
    x_prev = jnp.concatenate([state["shift"][:, None, :], x[:, :-1, :]], 1)
    r, k, v, g, w = R._projections(p, x, x_prev, x.dtype)
    resh = lambda t: t.reshape(B, S, H, hd)
    y_k, _ = wkv6(resh(r), resh(k), resh(v), resh(w.astype(x.dtype)),
                  p["bonus_u"], state["wkv"], chunk=16, interpret=True)
    y_k = R._group_norm(y_k.reshape(B * S, d), p["ln_x_scale"], H
                        ).reshape(B, S, d)
    y_k = y_k * jax.nn.silu(g)
    y_k = y_k @ p["wo"].astype(x.dtype)
    np.testing.assert_allclose(y_k, y_model, rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(64, 128), (3, 17, 256), (1, 8, 512)])
def test_rmsnorm_matches_ref(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], shape, dtype)
    scale = jax.random.normal(ks[1], (shape[-1],), dtype) * 0.1 + 1.0
    np.testing.assert_allclose(
        np.asarray(rmsnorm(x, scale, interpret=True), np.float32),
        np.asarray(rmsnorm_ref(x, scale), np.float32), **_tol(dtype))


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 64), st.sampled_from([128, 256, 384]))
def test_rmsnorm_property(rows, d):
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, d), jnp.float32)
    scale = jnp.ones((d,))
    got = rmsnorm(x, scale, interpret=True)
    np.testing.assert_allclose(got, rmsnorm_ref(x, scale), rtol=2e-5,
                               atol=2e-5)
    # invariant: output row RMS ~= 1 for unit scale
    rms = np.sqrt(np.mean(np.asarray(got) ** 2, axis=-1))
    np.testing.assert_allclose(rms, 1.0, atol=1e-2)
