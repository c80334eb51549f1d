"""The Pallas selective scan (``kernels/selective_scan``) in interpret mode
against the model's XLA scan, its gradient through the ``custom_vjp``, the
predicate that decides which of the two the prefill runs, and the serving
engine's ``scan_kernel_tokens`` counter that reports it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import repro.models.mamba as M
from repro.configs import get_config
from repro.kernels.selective_scan import ops as scan_ops
from repro.kernels.selective_scan.kernel import selective_scan_bsd
from repro.kernels.selective_scan.ref import selective_scan_ref
from repro.models import init_lm
from repro.runtime.serving import ServingEngine
from repro.sharding.context import sharding_rules


def _inputs(B, S, inner, N, dtype, seed, h0_scale):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(k[0], (B, S, inner)) - 2.0)
    u = jax.random.normal(k[1], (B, S, inner))
    Bm = jax.random.normal(k[2], (B, S, N))
    Cm = jax.random.normal(k[3], (B, S, N))
    A_T = -jnp.exp(jax.random.normal(k[4], (N, inner)))
    h0 = h0_scale * jax.random.normal(k[5], (B, N, inner))
    return (dt.astype(dtype), u.astype(dtype), Bm.astype(dtype),
            Cm.astype(dtype), A_T, h0)


@pytest.fixture
def scan_chunk(monkeypatch):
    """Sets the kernel's time chunk; jit caches are cleared around it, as
    the wrapper reads the chunk when it traces."""
    def set_chunk(chunk):
        monkeypatch.setattr(scan_ops, "SCAN_CHUNK", chunk)
        jax.clear_caches()
    yield set_chunk
    jax.clear_caches()


def _widths(arch):
    cfg = get_config(arch, reduced=True)
    return cfg.ssm.expand * cfg.d_model, cfg.ssm.state_dim


@pytest.mark.parametrize("arch,S,chunk,dtype,h0_scale", [
    ("jamba2_3b", 37, 32, jnp.float32, 1.0),     # S not a multiple
    ("hymba_1_5b", 37, 32, jnp.float32, 1.0),
    ("jamba2_3b", 64, 32, jnp.float32, 0.0),     # whole chunks, h0 = 0
    ("jamba2_3b", 37, 32, jnp.bfloat16, 1.0),    # bf16 Δ, u, B, C
    ("hymba_1_5b", 48, 256, jnp.bfloat16, 1.0),  # one chunk
])
def test_kernel_equals_the_xla_scan(arch, S, chunk, dtype, h0_scale,
                                    scan_chunk):
    """y and the last state equal the XLA scan's on the same inputs, read
    in float32 by both, to 1e-5."""
    scan_chunk(chunk)
    inner, N = _widths(arch)
    args = _inputs(2, S, inner, N, dtype, seed=S + inner, h0_scale=h0_scale)
    y, h = scan_ops.selective_scan(*args, interpret=True)
    y0, h0 = M._xla_scan(*args)
    assert y.dtype == dtype and y.shape == y0.shape and h.dtype == jnp.float32
    tol = 1e-5 if dtype == jnp.float32 else 1e-2   # y is rounded to bf16
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y0, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h0), rtol=1e-5,
                               atol=1e-5)


def test_kernel_pads_the_state_to_whole_sublanes():
    """A state of 12 is padded to 16 with zero rows that add nothing."""
    args = _inputs(2, 40, 256, 12, jnp.float32, seed=3, h0_scale=1.0)
    y, h = scan_ops.selective_scan(*args, interpret=True)
    y0, h0 = M._xla_scan(*args)
    assert h.shape == h0.shape == (2, 12, 256)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h0), rtol=1e-5,
                               atol=1e-5)


def test_ref_equals_the_xla_scan():
    args = _inputs(2, 37, 128, 4, jnp.float32, seed=1, h0_scale=1.0)
    for a, b in zip(selective_scan_ref(*args), M._xla_scan(*args)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


def test_kernel_refuses_shapes_it_does_not_tile():
    """A chunk shorter than the steps one loop iteration unrolls would
    leave steps unrun: refused, as is a state off whole sublanes."""
    args = _inputs(1, 32, 128, 16, jnp.float32, seed=2, h0_scale=1.0)
    with pytest.raises(ValueError):
        selective_scan_bsd(*args, block=128, chunk=16, interpret=True)
    args = _inputs(1, 32, 128, 12, jnp.float32, seed=2, h0_scale=1.0)
    with pytest.raises(ValueError):
        selective_scan_bsd(*args, block=128, chunk=32, interpret=True)


def test_channel_block_follows_the_width():
    assert scan_ops.channel_block(5120) == 1024
    assert scan_ops.channel_block(3200) == 640
    assert scan_ops.channel_block(128) == 128
    with pytest.raises(ValueError):
        scan_ops.channel_block(200)


@pytest.fixture
def kernel_forced(monkeypatch):
    """The prefill takes the kernel (in interpret mode) off the TPU."""
    monkeypatch.setattr(M, "scan_kernel_engaged", lambda cfg: True)


@pytest.mark.parametrize("arch", ["jamba2_3b", "hymba_1_5b"])
def test_kernel_gradient_equals_the_step_recurrences(arch, kernel_forced,
                                                     scan_chunk):
    """Through the ``custom_vjp``, the gradient of the prefill mixer's
    outputs over S = 37 steps equals that of 37 decode steps (float32, so
    1e-4 relative), in the inputs and in the state matrix."""
    scan_chunk(32)
    cfg = get_config(arch, reduced=True)
    p = M.init_mamba(jax.random.PRNGKey(5), cfg)
    B, S = 2, 37
    x = jax.random.normal(jax.random.PRNGKey(6), (B, S, cfg.d_model))
    state = M.init_mamba_state(cfg, B)
    state["h"] = jax.random.normal(jax.random.PRNGKey(8), state["h"].shape)
    w = jax.random.normal(jax.random.PRNGKey(7), (B, S, cfg.d_model))

    def seq(x, p):
        y, st = M.apply_mamba_seq(cfg, p, x, state)
        return jnp.sum(y * w) + jnp.sum(st["h"])

    def steps(x, p):
        total, st = 0.0, state
        for t in range(S):
            yt, st = M.apply_mamba_step(cfg, p, x[:, t:t + 1], st)
            total = total + jnp.sum(yt * w[:, t:t + 1])
        return total + jnp.sum(st["h"])

    with jax.default_matmul_precision("highest"):
        (gx1, gp1), (gx2, gp2) = (jax.grad(f, argnums=(0, 1))(x, p)
                                  for f in (seq, steps))
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(gp1["A_log"]),
                               np.asarray(gp2["A_log"]), rtol=1e-4, atol=1e-5)


def test_kernel_prefill_equals_the_xla_prefill(monkeypatch):
    """``apply_mamba_seq`` gives the same outputs and state on both paths."""
    cfg = get_config("jamba2_3b", reduced=True)
    p = M.init_mamba(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 37, cfg.d_model))
    state = M.init_mamba_state(cfg, 2)
    state["h"] = jax.random.normal(jax.random.PRNGKey(4), state["h"].shape)
    with jax.default_matmul_precision("highest"):
        y0, s0 = M.apply_mamba_seq(cfg, p, x, state)
        monkeypatch.setattr(M, "scan_kernel_engaged", lambda cfg: True)
        y1, s1 = M.apply_mamba_seq(cfg, p, x, state)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1["h"]), np.asarray(s0["h"]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- dispatch
@pytest.mark.parametrize("backend,mesh,d_model,engaged", [
    ("cpu", False, 2560, False),      # off the TPU
    ("tpu", True, 2560, False),       # a sharding mesh is active
    ("tpu", False, 100, False),       # inner 200: not whole 128-lane tiles
    ("tpu", False, 2560, True),       # Jamba's 5120
    ("tpu", False, 1600, True),       # Hymba's 3200
])
def test_scan_kernel_predicate(backend, mesh, d_model, engaged, monkeypatch):
    cfg = get_config("jamba2_3b")
    cfg = dataclasses.replace(cfg, d_model=d_model)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if mesh:
        m = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        with sharding_rules(m):
            assert M.scan_kernel_engaged(cfg) is engaged
    else:
        assert M.scan_kernel_engaged(cfg) is engaged


def test_cpu_prefill_takes_the_xla_scan():
    assert not M.scan_kernel_engaged(get_config("jamba2_3b"))


def _served_stats(cfg, prompt=24, new=4):
    params = init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    with ServingEngine(cfg, params, batch_size=2, max_new_tokens=new) as eng:
        reqs = [eng.submit(rng.integers(0, cfg.vocab, prompt)) for _ in range(3)]
        eng.flush(force=True)
        eng.collect()
        st = eng.stats()["serving"][(prompt, new)]
    return st, np.stack([r.result for r in reqs])


def test_scan_kernel_tokens_is_zero_on_the_cpu():
    st, _ = _served_stats(get_config("jamba2_3b", reduced=True))
    assert st["prefill_tokens"] == 2 * 2 * 24
    assert st["scan_kernel_tokens"] == 0


def test_scan_kernel_tokens_counts_the_kernels_prefill(monkeypatch):
    """With the predicate forced true the engine's prefill runs the kernel
    in interpret mode, counts every prompt token, and serves the tokens
    the XLA scan serves."""
    cfg = get_config("jamba2_3b", reduced=True)
    _, want = _served_stats(cfg)
    monkeypatch.setattr(M, "scan_kernel_engaged", lambda cfg: True)
    st, got = _served_stats(cfg)
    assert st["scan_kernel_tokens"] == st["prefill_tokens"] == 2 * 2 * 24
    np.testing.assert_array_equal(got, want)


def test_scan_kernel_tokens_is_zero_without_mamba_layers(kernel_forced):
    st, _ = _served_stats(get_config("qwen3_32b", reduced=True))
    assert st["scan_kernel_tokens"] == 0 and st["prefill_tokens"] > 0
