"""Serving correctness: step-by-step decode with ring-buffer caches must
reproduce the full-sequence forward logits, for every mixer family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import build_model
from repro.models.transformer import decoder_cache_shapes
from repro.train import serve_step as ss

EQUIV_ARCHS = ["codeqwen1.5-7b",        # plain GQA/MHA
               "gemma2-27b",            # local ring cache + global + softcap
               "yi-9b",                 # GQA 8:1 repeat
               "jamba-1.5-large-398b",  # mamba + attn + moe caches
               "xlstm-125m",            # mLSTM/sLSTM recurrent state
               "llama4-scout-17b-a16e"]  # MoE decode


def _decode_all_positions(model, cfg, params, tokens, max_seq):
    """Feed tokens one at a time; collect logits at each step."""
    B, S = tokens.shape
    caches = jax.tree.map(lambda sds: jnp.zeros(sds.shape, sds.dtype),
                          model.cache_shapes(B, max_seq, dtype=jnp.float32))
    caches = ss._reset_pos(caches)
    logits_steps = []
    for t in range(S):
        logits, caches = model.decode(
            params, {"token": tokens[:, t:t + 1],
                     "index": jnp.asarray(t, jnp.int32),
                     "caches": caches})
        logits_steps.append(np.asarray(logits[:, 0], np.float32))
    return np.stack(logits_steps, axis=1)    # (B, S, V)


@pytest.mark.parametrize("arch", EQUIV_ARCHS)
def test_decode_matches_full_forward(arch):
    cfg = smoke_config(arch)
    if cfg.moe is not None:
        # a decode step never fills an expert, while the full forward drops
        # tokens over capacity: compare the two where nothing is dropped
        m = cfg.moe
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            m, capacity_factor=m.num_experts / m.top_k))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    B, S = 2, 24
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    full, aux = model.train_logits(params, {"tokens": tokens})
    assert float(aux.get("dropped_frac", 0.0)) < 1e-6
    stepped = _decode_all_positions(model, cfg, params, tokens, max_seq=S + 4)
    np.testing.assert_allclose(stepped, np.asarray(full, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_local_ring_cache_beyond_window():
    """gemma2 local layers with cache capped at window: decoding past the
    window must still match the full windowed forward."""
    cfg = dataclasses.replace(smoke_config("gemma2-27b"), window=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    B, S = 1, 24                      # 3x the window
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                cfg.vocab_size)
    full, _ = model.train_logits(params, {"tokens": tokens})
    stepped = _decode_all_positions(model, cfg, params, tokens, max_seq=S)
    np.testing.assert_allclose(stepped, np.asarray(full, np.float32),
                               rtol=2e-2, atol=2e-2)
    # and the local layers' cache really is window-sized
    shapes = decoder_cache_shapes(cfg, B, S)
    assert shapes["0"]["k"].shape[2] == cfg.window      # local layer
    assert shapes["1"]["k"].shape[2] == S               # global layer


def test_whisper_decode_matches_full():
    cfg = smoke_config("whisper-small")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    B, S = 2, 12
    rng = jax.random.PRNGKey(3)
    frames = jax.random.normal(rng, (B, cfg.encoder_seq, cfg.d_model),
                               jnp.float32)
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    full, _ = model.train_logits(params, {"frames": frames,
                                          "tokens": tokens})
    # build decode caches: empty self + precomputed cross K/V
    from repro.models import whisper as W

    enc = W.encode(params, frames, cfg, lambda x, a: x)
    cross = W.build_cross_cache(params, enc)
    self_caches = jax.tree.map(
        lambda sds: jnp.zeros(sds.shape, jnp.float32),
        W.self_cache_shapes(cfg, B, S, jnp.float32))
    self_caches["pos"] = jnp.full(self_caches["pos"].shape, -1, jnp.int32)
    caches = {"self": self_caches, "cross": cross}
    outs = []
    for t in range(S):
        logits, caches = model.decode(
            params, {"token": tokens[:, t:t + 1],
                     "index": jnp.asarray(t, jnp.int32), "caches": caches})
        outs.append(np.asarray(logits[:, 0], np.float32))
    np.testing.assert_allclose(np.stack(outs, 1),
                               np.asarray(full, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_generate_is_deterministic_and_extends_prompt():
    cfg = smoke_config("xlstm-125m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 3,
                                cfg.vocab_size)
    out1 = ss.generate(model, cfg, params, prompt, steps=6, max_seq=16)
    out2 = ss.generate(model, cfg, params, prompt, steps=6, max_seq=16)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (2, 11)
    np.testing.assert_array_equal(np.asarray(out1[:, :5]),
                                  np.asarray(prompt))
