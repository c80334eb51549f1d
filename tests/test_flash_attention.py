"""The Pallas flash-attention kernel (``kernels/flash_attention``) in
interpret mode against the materialised-softmax oracle and the XLA chunked
path; the gradient of ``attend_full`` through its ``custom_vjp``; the
predicate ``flash_kernel_engaged`` that decides where it runs; and the
serving engine's ``flash_kernel_tokens`` counter that reports it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import get_config
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.ref import attention_ref
from repro.models import attention as A
from repro.models import init_lm
from repro.runtime.serving import ServingEngine
from repro.sharding.context import sharding_rules


class _Cfg:
    logit_softcap = 0.0


def _qkv(B, S, H, Hkv, hd, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, hd), dtype),
            jax.random.normal(ks[1], (B, S, Hkv, hd), dtype),
            jax.random.normal(ks[2], (B, S, Hkv, hd), dtype))


@pytest.mark.parametrize("B,S,H,Hkv,hd,window,softcap,block_q,block_k", [
    (1, 512, 20, 1, 128, 0, 0.0, 32, 128),     # MQA 20/1, blocks skipped
    (2, 512, 25, 5, 64, 0, 0.0, 128, 128),     # GQA 25/5 at hd 64
    (1, 2048, 2, 1, 64, 1024, 0.0, 128, 256),  # window 1024, skipped below
    (1, 256, 4, 1, 64, 0, 30.0, 64, 128),      # softcap (gemma-style)
    (1, 256, 8, 2, 32, 96, 20.0, 64, 128),     # window + softcap
])
def test_kernel_matches_references(B, S, H, Hkv, hd, window, softcap,
                                   block_q, block_k):
    """float32: the output against ``attention_ref`` and the chunked path,
    the log-sum-exp against ``_chunked_fwd_impl``'s, to 2e-5."""
    q, k, v = _qkv(B, S, H, Hkv, hd, jnp.float32)
    got, lse = flash_ops.flash_attention(
        q, k, v, window=window, softcap=softcap, block_q=block_q,
        block_k=block_k, with_lse=True, interpret=True)
    cfg = dataclasses.replace(get_config("qwen3_32b", reduced=True),
                              logit_softcap=softcap)
    with jax.default_matmul_precision("highest"):
        ref = attention_ref(q, k, v, window=window, softcap=softcap)
        chunked = A._sdpa_chunked(cfg, q, k, v, window=window, q_chunk=128,
                                  kv_chunk=128)
        _, lse_ref = A._chunked_fwd_impl(q, k, v, True, window, softcap,
                                         128, 128)
    tol = dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, ref, **tol)
    np.testing.assert_allclose(got.reshape(B, S, -1), chunked, **tol)
    np.testing.assert_allclose(lse, lse_ref, **tol)


def test_kernel_bf16_matches_the_float32_oracle():
    """bfloat16 MQA 20/1 at hd 128: dots on bf16 operands with float32
    accumulation stay within the bf16 tolerance of the f32 oracle and as
    close to it as the XLA chunked path on the same operands."""
    q, k, v = _qkv(1, 512, 20, 1, 128, jnp.bfloat16, seed=4)
    got = flash_ops.flash_attention(q, k, v, block_q=64, block_k=128,
                                    interpret=True)
    f32 = lambda a: np.asarray(a, np.float32)
    with jax.default_matmul_precision("highest"):
        ref = f32(attention_ref(*(a.astype(jnp.float32) for a in (q, k, v))))
    chunked = f32(A._sdpa_chunked(_Cfg, q, k, v, q_chunk=128, kv_chunk=128))
    np.testing.assert_allclose(f32(got), ref, rtol=2e-2, atol=2e-2)
    err = np.abs(f32(got).reshape(chunked.shape) - ref.reshape(chunked.shape))
    assert err.max() <= 1.5 * np.abs(chunked - ref.reshape(chunked.shape)).max()


@pytest.mark.parametrize("S,group,blocks", [
    (8192, 20, (256, 1024, 512)),    # Jamba2-3B: 5120 rows a query block
    (2048, 20, (256, 1024, 512)),
    (2048, 5, (1024, 1024, 512)),    # Hymba-1.5B: 25 heads over 5
    (4096, 1, (4096, 1024, 512)),    # MHA
    (2050, 20, None),                # no 128-lane K/V tile divides it
])
def test_block_sizes_follow_the_shapes(S, group, blocks):
    """(block_q, block_k) from ``block_sizes`` and the rows of a score tile
    that divide the query block's block_q x group rows."""
    picked = flash_ops.block_sizes(S, S, group)
    if blocks is None:
        assert picked is None
    else:
        assert (*picked, flash_ops._chunk(picked[0] * group)) == blocks


def test_kernel_refuses_blocks_that_do_not_tile():
    q, k, v = _qkv(1, 256, 4, 1, 64, jnp.float32)
    with pytest.raises(ValueError):
        flash_ops.flash_attention(q, k, v, block_q=96, block_k=128,
                                  interpret=True)


# ---------------------------------------------------------------- the path
@pytest.fixture
def kernel_forced(monkeypatch):
    """``attend_full`` takes the kernel (in interpret mode) off the TPU."""
    monkeypatch.setattr(A, "flash_kernel_engaged",
                        lambda cfg, S, bidirectional=False: True)


@pytest.mark.parametrize("arch,window", [("jamba2_3b", 0),
                                         ("qwen3_32b", 0),
                                         ("hymba_1_5b", 384)])
def test_gradient_through_attend_full_equals_the_chunked_path(
        arch, window, monkeypatch):
    """The kernel's ``custom_vjp`` (forward by the kernel, backward by
    ``_chunked_bwd`` from its log-sum-exp) gives the chunked path's output
    and gradients, in the input and in every weight, at 2048 tokens."""
    cfg = get_config(arch, reduced=True)
    p = A.init_attention(jax.random.PRNGKey(0), cfg)
    S = A.CHUNKED_THRESHOLD
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, cfg.d_model))
    w = jax.random.normal(jax.random.PRNGKey(2), (1, S, cfg.d_model))
    pos = jnp.arange(S)[None]

    def loss(x, p):
        return jnp.sum(A.attend_full(cfg, p, x, pos, window=window)[0] * w)

    with jax.default_matmul_precision("highest"):
        y0 = A.attend_full(cfg, p, x, pos, window=window)[0]
        g0 = jax.grad(loss, argnums=(0, 1))(x, p)
        monkeypatch.setattr(A, "flash_kernel_engaged",
                            lambda cfg, S, bidirectional=False: True)
        y1 = A.attend_full(cfg, p, x, pos, window=window)[0]
        g1 = jax.grad(loss, argnums=(0, 1))(x, p)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(np.abs(b).max()))


@pytest.mark.parametrize("backend,mesh,S,bidirectional,engaged", [
    ("cpu", False, 8192, False, False),     # off the TPU
    ("tpu", True, 8192, False, False),      # a sharding mesh is active
    ("tpu", False, 2047, False, False),     # below CHUNKED_THRESHOLD
    ("tpu", False, 8192, True, False),      # bidirectional (an encoder)
    ("tpu", False, 2050, False, False),     # no block divides the length
    ("tpu", False, 2048, False, True),
    ("tpu", False, 8192, False, True),      # Jamba2-3B's long prompts
])
def test_flash_kernel_predicate(backend, mesh, S, bidirectional, engaged,
                                monkeypatch):
    cfg = get_config("jamba2_3b")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if mesh:
        m = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        with sharding_rules(m):
            got = A.flash_kernel_engaged(cfg, S, bidirectional=bidirectional)
    else:
        got = A.flash_kernel_engaged(cfg, S, bidirectional=bidirectional)
    assert got is engaged


def test_cpu_prefill_takes_the_xla_path():
    assert not A.flash_kernel_engaged(get_config("jamba2_3b"), 8192)


# ---------------------------------------------------------------- counter
def _served_stats(cfg, prompt=128, new=4):
    params = init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    with ServingEngine(cfg, params, batch_size=2, max_new_tokens=new) as eng:
        reqs = [eng.submit(rng.integers(0, cfg.vocab, prompt))
                for _ in range(3)]
        eng.flush(force=True)
        eng.collect()
        st = eng.stats()["serving"][(prompt, new)]
    return st, np.stack([r.result for r in reqs])


def test_flash_kernel_tokens_is_zero_on_the_cpu():
    st, _ = _served_stats(get_config("jamba2_3b", reduced=True))
    assert st["prefill_tokens"] == 2 * 2 * 128
    assert st["flash_kernel_tokens"] == 0


@pytest.mark.parametrize("arch", ["jamba2_3b", "qwen3_32b"])
def test_flash_kernel_tokens_counts_the_kernels_prefill(arch, monkeypatch):
    """With the predicate forced true the engine's prefill runs the kernel
    in interpret mode, counts every prompt token, and serves the tokens
    the XLA path serves."""
    cfg = get_config(arch, reduced=True)
    _, want = _served_stats(cfg)
    monkeypatch.setattr(A, "flash_kernel_engaged",
                        lambda cfg, S, bidirectional=False: True)
    st, got = _served_stats(cfg)
    assert st["flash_kernel_tokens"] == st["prefill_tokens"] == 2 * 2 * 128
    np.testing.assert_array_equal(got, want)


def test_flash_kernel_tokens_is_zero_without_attention_layers(kernel_forced):
    st, _ = _served_stats(get_config("rwkv6_1_6b", reduced=True))
    assert st["flash_kernel_tokens"] == 0 and st["prefill_tokens"] > 0
