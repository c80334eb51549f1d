"""AI21-Jamba2-3B through the normal path, on the CPU at a reduced size.

The plain float32 reference (``chipbench/reference/jamba2_3b.py``) is the
yardstick: prefill then cached decode, through the serving engine's own
jitted steps and through the engine itself, against its full teacher-forced
forward pass.  The chunked Mamba scan is checked against the step-by-step
decode recurrence for Jamba's mixer and Hymba's, and the prefill of both
hybrid models against holding any (batch, seq, inner, state) value.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.mamba as M
import repro.models.transformer as TR
from repro.configs import get_config
from repro.models import (forward_prefill, init_cache, init_lm, layer_kinds)
from repro.runtime.serving import ServingEngine

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.reference import jamba2_3b as ref  # noqa: E402

# One full period of 14 layers at a width the CPU holds.
SIZES = dict(n_layers=14, d_model=64, n_heads=4, n_kv_heads=1, head_dim=16,
             d_ff=128, vocab=512, attn_period=14, attn_offset=7,
             ssm_state=4, ssm_expand=2, ssm_conv=4, dt_rank=8,
             norm_eps=1e-6, init={"weight": 0.1, "dt": 0.05, "conv": 0.2})


def small_config():
    base = get_config("jamba2_3b")
    s = SIZES
    return dataclasses.replace(
        base, n_layers=s["n_layers"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
        head_dim=s["head_dim"], d_ff=s["d_ff"], vocab=s["vocab"],
        ssm=dataclasses.replace(base.ssm, state_dim=s["ssm_state"],
                                dt_rank=s["dt_rank"]))


def test_attention_lands_on_layers_7_and_21_of_28():
    cfg = get_config("jamba2_3b")
    kinds = layer_kinds(cfg) * (cfg.n_layers // cfg.layer_group)
    assert len(kinds) == 28
    assert [i for i, k in enumerate(kinds) if k == "attn_global"] == [7, 21]
    assert set(kinds) == {"attn_global", "mamba"}
    assert not cfg.rope and cfg.tie_embeddings and cfg.moe is None
    # 3.03 B parameters: 6.05 GB in bf16, whole on one 16 GB chip.
    assert 3.0e9 < cfg.param_count() < 3.06e9


def test_reference_weights_are_the_programs_layout():
    cfg = small_config()
    want = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: ref.make_params(3, SIZES))
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))


def _teacher_forced(toks, gen, prompt, new):
    seq = np.concatenate([toks, gen[:, :-1]], 1)
    pos = np.tile(np.arange(prompt - 1, prompt - 1 + new), (len(toks), 1))
    return seq, pos


@pytest.fixture
def f32_embed(monkeypatch):
    # The program embeds in bfloat16; with float32 weights throughout, it
    # and the reference compute the same sums.
    monkeypatch.setattr(TR, "_embed", lambda cfg, params, tokens,
                        frontend=None: params["embed"][tokens])


@pytest.mark.parametrize("prompt,new", [(24, 10), (37, 6)])
def test_engine_steps_match_the_float32_reference(prompt, new, f32_embed):
    """The serving engine's jitted prefill and decode steps, over a float32
    cache, give the reference's logits at every teacher-forced position.
    Both sides are float32 at HIGHEST precision and differ only in the
    order of sums (the chunked scan, blocked attention), so 1e-4."""
    cfg, B = small_config(), 2
    params = ref.make_params(11, SIZES, dtype=jnp.float32)
    eng = ServingEngine(cfg, params, batch_size=B, max_new_tokens=new)
    toks = np.random.default_rng(prompt).integers(
        0, SIZES["vocab"], (B, prompt)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        cache = init_cache(cfg, B, prompt + new, dtype=jnp.float32)
        logits, cache = eng._prefill(params, {"tokens": toks}, cache)
        outs, gen = [logits], [jnp.argmax(logits, -1)]
        for i in range(new - 1):
            nxt, lg, cache = eng._decode(
                params, gen[-1][:, None].astype(jnp.int32), cache,
                jnp.int32(prompt + i))
            outs.append(lg)
            gen.append(nxt[:, 0])
    eng.close()
    prog = np.stack([np.asarray(o) for o in outs], 1)
    seq, pos = _teacher_forced(toks, np.stack(gen, 1), prompt, new)
    want = ref.logits_at(params, seq, pos, SIZES)
    assert np.abs(prog - want).max() < 1e-4


def test_served_tokens_are_the_references_best(f32_embed):
    """Requests through ``ServingEngine`` (prefill, then decode through the
    cache it keeps): every served token's reference logit is within 1e-2
    of the reference's best at its position.  The engine keeps keys and
    values in bfloat16 (relative rounding 2^-9 in one layer of 14), which
    can reorder logits that lie closer than that; a wrong token anywhere
    would miss by the logits' spread, about 1."""
    cfg, prompt, new = small_config(), 30, 8
    params = ref.make_params(5, SIZES, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, SIZES["vocab"], prompt).astype(np.int32)
               for _ in range(3)]
    with jax.default_matmul_precision("highest"):
        with ServingEngine(cfg, params, batch_size=2,
                           max_new_tokens=new) as eng:
            reqs = [eng.submit(p) for p in prompts]
            eng.flush(force=True)
            eng.collect()
    gen = np.stack([r.result for r in reqs])
    seq, pos = _teacher_forced(np.stack(prompts), gen, prompt, new)
    want = ref.logits_at(params, seq, pos, SIZES)
    picked = np.take_along_axis(want, gen[:, :, None], -1)[..., 0]
    assert (want.max(-1) - picked).max() < 1e-2


@pytest.mark.parametrize("arch", ["jamba2_3b", "hymba_1_5b"])
def test_chunked_scan_equals_the_step_recurrence(arch, monkeypatch):
    """The prefill mixer over S = 37 steps in chunks of 16 (S not a
    multiple of the chunk) equals 37 decode steps of the O(1) update, in
    outputs and in the state it leaves; float32, so 1e-5."""
    monkeypatch.setattr(M, "SCAN_CHUNK", 16)
    cfg = get_config(arch, reduced=True)
    p = M.init_mamba(jax.random.PRNGKey(2), cfg)
    p = dict(p, dt_bias=p["dt_bias"] - 2.0)        # longer memory
    B, S = 2, 37
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model))
    state = M.init_mamba_state(cfg, B)
    state["h"] = jax.random.normal(jax.random.PRNGKey(4), state["h"].shape)
    with jax.default_matmul_precision("highest"):
        y, st = M.apply_mamba_seq(cfg, p, x, state)
        ys, s1 = [], state
        for t in range(S):
            yt, s1 = M.apply_mamba_step(cfg, p, x[:, t:t + 1], s1)
            ys.append(yt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        jnp.concatenate(ys, 1)), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(st["h"]), np.asarray(s1["h"]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(st["conv"]),
                               np.asarray(s1["conv"]), atol=1e-6)


@pytest.mark.parametrize("arch", ["jamba2_3b", "hymba_1_5b"])
def test_chunked_scan_gradient_equals_the_step_recurrences(arch,
                                                           monkeypatch):
    """Training differentiates the checkpointed chunks: the gradient of the
    prefill mixer's outputs over S = 37 steps in chunks of 16 equals that
    of the step-by-step recurrence (float32, so 1e-4 relative)."""
    monkeypatch.setattr(M, "SCAN_CHUNK", 16)
    cfg = get_config(arch, reduced=True)
    p = M.init_mamba(jax.random.PRNGKey(5), cfg)
    B, S = 2, 37
    x = jax.random.normal(jax.random.PRNGKey(6), (B, S, cfg.d_model))
    state = M.init_mamba_state(cfg, B)
    w = jax.random.normal(jax.random.PRNGKey(7), (B, S, cfg.d_model))

    def seq(x):
        return jnp.sum(M.apply_mamba_seq(cfg, p, x, state)[0] * w)

    def steps(x):
        total, st = 0.0, state
        for t in range(S):
            yt, st = M.apply_mamba_step(cfg, p, x[:, t:t + 1], st)
            total = total + jnp.sum(yt * w[:, t:t + 1])
        return total

    with jax.default_matmul_precision("highest"):
        g1, g2 = jax.grad(seq)(x), jax.grad(steps)(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4,
                               atol=1e-5)


def _avals(jaxpr):
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v, "aval") and hasattr(v.aval, "shape"):
                yield v.aval.shape
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


@pytest.mark.parametrize("arch", ["jamba2_3b", "hymba_1_5b"])
def test_prefill_never_holds_a_batch_seq_inner_state_array(arch):
    cfg = get_config(arch, reduced=True)
    B, S = 2, 1024
    inner, N = cfg.ssm.expand * cfg.d_model, cfg.ssm.state_dim
    params = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: init_cache(cfg, B, S + 8))
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    closed = jax.make_jaxpr(lambda p, t, c: forward_prefill(
        cfg, p, {"tokens": t}, c))(params, toks, cache)
    shapes = list(_avals(closed.jaxpr))
    assert any(inner in s and N in s for s in shapes)   # the scan's state
    big = [s for s in shapes if inner in s and N in s
           and int(np.prod(s)) >= B * S * inner * N]
    assert not big, big
