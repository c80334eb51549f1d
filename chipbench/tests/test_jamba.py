"""Jamba2-3B's benchmark files at sizes the CPU holds: the configuration
file against the catalog row and the registered config, the FLOP counts,
the plain reference against the program's prefill and cached decode in
float32, and whole runs of ``jamba2-3b-longdoc``, sound and with a token
altered where the engine produced it."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.counts import jamba2_3b as C
from chipbench.reference import jamba2_3b as R

from .conftest import CHIP, ROOT
from .test_faults import _plant, _serving_kernel

FILE = json.loads((ROOT / "chipbench/configs/jamba2_3b.json").read_text())
SIZES = FILE["sizes"]

# A whole period of 14 layers at a width the CPU holds; logits spread as
# at published widths (0.02 x sqrt(2560) = 0.125 x sqrt(64)).
TINY_SIZES = dict(SIZES, n_layers=14, d_model=64, n_heads=4, n_kv_heads=1,
                  head_dim=16, d_ff=128, vocab=4096, ssm_state=4, dt_rank=8,
                  init={"weight": 0.125, "dt": 0.05, "conv": 0.2})
TINY = {
    # This tiny model's own limit, from CPU readings on seeds 1-4: program
    # (bfloat16) at most 0.087, float8 control at least 0.47.
    "config": {"sizes": TINY_SIZES, "limits": {"serve_gap": 0.2}},
    "traffic": {"prompt_lens": [[40, 0.5], [24, 0.5]],
                "new_tokens": [[6, 1.0]], "trace_s": 0.2},
}


def test_the_file_holds_the_catalog_row_and_the_registered_config():
    from repro.configs import get_config
    cfg = get_config(FILE["arch"])
    assert FILE["reduced"] == [] and FILE["departures"] == []
    s = SIZES
    assert (s["n_layers"], s["d_model"], s["d_ff"], s["vocab"]) == (
        FILE["num_hidden_layers"], FILE["hidden_size"],
        FILE["intermediate_size"], FILE["vocab_size"])
    assert (s["n_heads"], s["n_kv_heads"]) == (
        FILE["num_attention_heads"], FILE["num_key_value_heads"])
    assert s["head_dim"] == FILE["hidden_size"] // FILE["num_attention_heads"]
    assert (s["attn_period"], s["attn_offset"]) == (
        FILE["attn_layer_period"], FILE["attn_layer_offset"])
    assert (s["ssm_state"], s["ssm_expand"], s["ssm_conv"], s["dt_rank"]) == (
        FILE["mamba_d_state"], FILE["mamba_expand"], FILE["mamba_d_conv"],
        FILE["mamba_dt_rank"])
    assert s["norm_eps"] == FILE["rms_norm_eps"]
    assert FILE["num_experts"] == 1 and FILE["tie_word_embeddings"]
    assert FILE["mamba_conv_bias"] and not FILE["mamba_proj_bias"]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
            cfg.d_ff, cfg.vocab, cfg.attn_period, cfg.attn_offset) == (
        s["n_layers"], s["d_model"], s["n_heads"], s["n_kv_heads"],
        s["head_dim"], s["d_ff"], s["vocab"], s["attn_period"],
        s["attn_offset"])
    assert (cfg.ssm.state_dim, cfg.ssm.expand, cfg.ssm.conv_dim,
            cfg.ssm.dt_rank) == (s["ssm_state"], s["ssm_expand"],
                                 s["ssm_conv"], s["dt_rank"])
    assert cfg.ssm.inner_norms and cfg.ssm.conv_bias and not cfg.rope


def _weights(s):
    return sum(int(np.prod(shape)) for path, (shape, _) in
               R.leaf_specs(s).items()
               if not path.endswith(("norm/scale", "_norm", "A_log", "/D",
                                     "dt_bias", "conv_b")))


def test_prefill_is_two_flops_per_weight_per_token_plus_attention_and_scan():
    toks = 4 * 2048
    f = C.prefill_flops(SIZES, 4, 2048)
    # Every weight but the embedding multiplies each token once; the head
    # (the embedding, transposed) multiplies the last token of each row.
    dense = 2 * (_weights(SIZES) - SIZES["vocab"] * SIZES["d_model"]) * toks
    assert dense < f < 1.1 * dense
    assert _weights(SIZES) == pytest.approx(3.03e9, rel=0.01)


def test_decode_grows_with_every_earlier_key_in_the_two_attention_layers():
    a, b = C.decode_flops(SIZES, 4, 100), C.decode_flops(SIZES, 4, 101)
    assert b - a == 4 * 2 * 4 * SIZES["n_heads"] * SIZES["head_dim"]
    mean = C.decode_mean_flops(SIZES, 4, 8192, 64)
    assert mean == pytest.approx(C.decode_flops(SIZES, 4, 8192 + 31))


@pytest.mark.parametrize("prompt,new", [(24, 12), (40, 6)])
def test_reference_matches_prefill_and_cached_decode(prompt, new,
                                                     monkeypatch):
    import repro.models.transformer as TR
    from repro.models import forward_decode, forward_prefill, init_cache

    from chipbench.generators import offline, serve

    s = TINY_SIZES
    arch = offline.program_config({"arch": "jamba2_3b", "sizes": s})
    params = R.make_params(7, s, dtype=jnp.float32)
    serve._check_layout(arch, params)
    # The program embeds in bfloat16; in float32 throughout, it and the
    # reference compute the same sums.
    monkeypatch.setattr(TR, "_embed",
                        lambda cfg, params, tokens, frontend=None:
                        params["embed"][tokens])
    B = 2
    toks = np.random.default_rng(0).integers(0, s["vocab"], (B, prompt)
                                             ).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        cache = init_cache(arch, B, prompt + new, dtype=jnp.float32)
        logits, cache = forward_prefill(arch, params, {"tokens": toks}, cache)
        outs, gen = [logits], [jnp.argmax(logits, -1)]
        for i in range(new - 1):
            lg, cache = forward_decode(
                arch, params, gen[-1][:, None].astype(jnp.int32), cache,
                jnp.int32(prompt + i))
            outs.append(lg)
            gen.append(jnp.argmax(lg, -1))
    prog = np.stack([np.asarray(o) for o in outs], 1)
    gen = np.stack([np.asarray(g) for g in gen], 1)
    seq = np.concatenate([toks, gen[:, :-1]], 1)
    pos = np.tile(np.arange(prompt - 1, prompt - 1 + new), (B, 1))
    want = R.logits_at(params, seq, pos, s)
    assert np.abs(prog - want).max() < 1e-4


# ----------------------------------------------------------------------
@pytest.fixture
def run_longdoc(monkeypatch):
    """Run ``jamba2-3b-longdoc`` through ``bench.execute`` on the CPU, at
    the sizes of ``TINY``."""
    from chipbench import bench, peaks

    load = bench.load_json

    def load_small(path):
        data = load(path)
        if path.parent.name == "configs" and data["name"] == "jamba2_3b":
            data.update(TINY["config"])
        elif path.parent.name == "traffic" and data["generator"] == "offline":
            data.update(TINY["traffic"])
        return data

    monkeypatch.setattr(bench, "load_json", load_small)
    monkeypatch.setattr(bench, "find_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(bench, "peaks", lambda kind: peaks.peaks(CHIP))
    monkeypatch.setattr(bench, "enable_cache", lambda: "")

    def run(seed: int = 2 ** 33 + 17, seconds: float = 1.0,
            traced: bool = False) -> dict:
        return bench.execute("jamba2-3b-longdoc", seed, seconds, traced,
                             t_start=time.perf_counter())
    return run


def test_a_sound_longdoc_run_is_correct_and_counts_its_work(run_longdoc):
    r = run_longdoc()
    assert r["correct"], r
    runs = r["notes"]["runs"]
    assert runs > 0 and r["attempted"] == 8 * runs and r["failed"] == 0
    assert r["metrics"]["dag_ms"]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0
    # One warm-up run and the window's, two batches of 4 a run; cache bytes
    # of the longer shape: K and V of the one attention layer in bf16.
    n = runs + 1
    assert r["notes"]["prefill_tokens.40x6"] == 4 * 40 * n
    assert r["notes"]["decode_steps.24x6"] == 5 * n
    assert r["notes"]["kv_cache_bytes.40x6"] == 2 * 4 * 46 * 16 * 2
    assert r["notes"]["ssm_state_bytes.40x6"] > 0


def test_a_traced_longdoc_run_is_correct(run_longdoc):
    r = run_longdoc(traced=True, seconds=0.5)
    assert r["correct"], r


def test_a_token_altered_where_produced_is_not_correct(run_longdoc,
                                                       monkeypatch):
    vocab = TINY_SIZES["vocab"]

    def after(e, v):
        if not _serving_kernel(e):
            return None
        out = np.asarray(v).copy()
        out[:, -1] = (out[:, -1] + 1) % vocab
        return out
    _plant(monkeypatch, after=after)
    assert not run_longdoc()["correct"]
