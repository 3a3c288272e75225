"""The plain reference against the program's own logits, on the two
reduced presets (qwen: QKV bias and a tied head; nemo: untied head and a
head size that is not d_model / heads), with the benchmark's weights.

The program computes in bfloat16 (weights, activations, KV) with float32
accumulation; the reference in float32 at HIGHEST. Over two layers the
program's logits carry a few bf16 roundings (2^-8 relative each), about
4% of the logits' spread (0.006 of 0.16 at seed 7), so they agree within
a tenth of it. The program's greedy tokens lie within 0.004 of the
reference's best; the fp8 control's picks 0.07-0.09 below it (CPU, seed
7), so five times the program's widest gap separates them."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights
from repro.configs import get_reduced
from repro.models import build_model

MAX_LEN = 64


def hf_shape(cfg) -> dict:
    return dict(hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
                head_dim=cfg.head_dim, num_attention_heads=cfg.n_heads,
                num_key_value_heads=cfg.n_kv_heads,
                num_hidden_layers=cfg.n_layers, vocab_size=cfg.vocab_size,
                rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps)


def steps(model):
    """The program's jitted prefill and cached decode step."""
    v = model.cfg.vocab_size
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, MAX_LEN))
    step = jax.jit(model.decode_step)
    return v, prefill, step


def program_logits(model, params, prompt, served) -> np.ndarray:
    """The program's logits at the positions that predict each served
    token: prefill, then one cached decode step per token."""
    v, prefill, step = steps(model)
    logits, state = prefill(params, jnp.asarray(prompt)[None])
    out = [logits[0, -1, :v]]
    cache_len = jnp.asarray(len(prompt), jnp.int32)
    for t in served[:-1]:
        logits, state = step(params, jnp.asarray([[t]], jnp.int32), state,
                             cache_len)
        out.append(logits[0, -1, :v])
        cache_len = cache_len + 1
    return np.asarray(jnp.stack(out), np.float32)


@pytest.fixture(scope="module", params=["qwen2.5-3b", "mistral-nemo-12b"])
def preset(request):
    cfg = get_reduced(request.param)
    model = build_model(cfg)
    params = weights.make_params(model.init, 7)
    return cfg, model, params


def sample(cfg, seed, n_prompt=24, n_served=12):
    rng = np.random.default_rng(seed)
    return (rng.integers(2, cfg.vocab_size, n_prompt).astype(np.int32),
            rng.integers(2, cfg.vocab_size, n_served).astype(np.int32))


def test_reference_matches_program_logits(preset):
    cfg, model, params = preset
    prompt, served = sample(cfg, 0)
    with jax.default_matmul_precision("highest"):
        got = program_logits(model, params, prompt, served)
    ref = reference.served_logits(hf_shape(cfg), params, prompt, served,
                                  n_out=16, step=16)
    assert ref.shape == got.shape == (len(served), cfg.vocab_size)
    assert ref.std() > 0.1                # logits spread, not all ~0
    assert np.abs(got - ref).max() < 0.1 * ref.std()


def test_widest_gap_of_the_program_and_of_the_control(preset):
    """The program's own greedy tokens lie within rounding of the
    reference's best; the fp8 control's picks lie far further below."""
    cfg, model, params = preset
    samples = []
    for seed in range(12):
        prompt, _ = sample(cfg, seed)
        toks, _ = model_greedy(model, params, prompt, 24)
        samples.append((prompt, toks))
    served, low = reference.token_gaps(hf_shape(cfg), params, samples,
                                       n_out=24, step=16, control=True)
    assert served.shape == low.shape == (12 * 24,)
    gap, control = served.max(), low.max()
    assert reference.summary(served)["widest"] == gap
    assert gap < 0.02
    assert control > 5 * max(gap, 0.005)


def model_greedy(model, params, prompt, n):
    """The program's greedy continuation, one cached step at a time."""
    v, prefill, step = steps(model)
    logits, state = prefill(params, jnp.asarray(prompt)[None])
    toks = [int(jnp.argmax(logits[0, -1, :v]))]
    cache_len = jnp.asarray(len(prompt), jnp.int32)
    for _ in range(n - 1):
        logits, state = step(
            params, jnp.asarray([[toks[-1]]], jnp.int32), state, cache_len)
        toks.append(int(jnp.argmax(logits[0, -1, :v])))
        cache_len = cache_len + 1
    return np.asarray(toks, np.int32), state
