"""Serving engine: greedy generation, determinism, EOS handling, and the
continuous-batching slot pool (equivalence, slot reuse, on-device decode)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import build_model
from repro.models.config import ModelConfig
from repro.serve.engine import Engine, EngineConfig
from repro.serve.scheduler import Request, Scheduler

TINY = ModelConfig(
    name="tiny-serve", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=128,
)


@pytest.fixture(scope="module")
def engine():
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    return Engine(model, params, EngineConfig(max_len=64, eos_token=1))


def test_generate_shapes_and_determinism(engine):
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 2,
                              TINY.vocab_size)
    out1, _ = engine.generate({"tokens": toks}, n_steps=6)
    out2, _ = engine.generate({"tokens": toks}, n_steps=6)
    assert out1.shape[0] == 2 and out1.shape[1] <= 6
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert int(out1.max()) < TINY.vocab_size


def test_generate_matches_teacher_forced_argmax(engine):
    """Greedy decode must equal argmax over the full-forward logits computed
    on the generated prefix — cache exactness at the engine level."""
    from repro.models import layers, transformer
    model, params = engine.model, engine.params
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 2,
                              TINY.vocab_size)
    gen, _ = engine.generate({"tokens": toks}, n_steps=4)
    seq = jnp.concatenate([toks, gen], axis=1)
    x, _, _ = transformer.forward(TINY, params, seq, remat=False)
    logits = layers.unembed_logits(params["tok"], x)
    for i in range(gen.shape[1]):
        pos = toks.shape[1] + i - 1
        want = int(jnp.argmax(logits[0, pos, :TINY.vocab_size]))
        got = int(gen[0, i])
        if got == 1:   # EOS fill after termination
            break
        assert got == want, (i, got, want)


def test_eos_stops_generation():
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, EngineConfig(max_len=64, eos_token=1))
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 4), 2,
                              TINY.vocab_size)
    out, _ = eng.generate({"tokens": toks}, n_steps=8)
    hit = np.where(np.asarray(out[0]) == 1)[0]
    if hit.size:   # everything after the first EOS must stay EOS
        assert (np.asarray(out[0])[hit[0]:] == 1).all()


# ------------------------------------------------- on-device decode loop

def test_no_per_token_host_sync(engine):
    """The decode loop must stay on-device: any implicit device->host
    transfer (the old per-token ``bool(done.all())``) faults under the
    transfer guard. Host reads happen only at drain boundaries, through
    the engine's counted fetch path."""
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 2,
                              TINY.vocab_size)
    with jax.transfer_guard_device_to_host("disallow"):
        out, _ = engine.generate({"tokens": toks}, n_steps=12)
    stats = engine.last_stats
    assert stats["decode_steps"] == 11
    # drain boundaries only: at most one sync per sync_interval chunk
    n_chunks = -(-11 // engine.ecfg.sync_interval)
    assert stats["host_syncs"] <= n_chunks
    assert out.shape[1] <= 12


def test_serve_no_per_token_host_sync(engine):
    rng = np.random.RandomState(0)
    sch = Scheduler(n_slots=2)
    for _ in range(4):
        sch.submit(rng.randint(2, TINY.vocab_size, size=6), 5)
    with jax.transfer_guard_device_to_host("disallow"):
        report = engine.serve(scheduler=sch)
    assert report.stats["drained"] == 4
    assert report.stats["host_syncs"] == report.stats["chunks"]


# ------------------------------------------------- continuous batching

def test_continuous_matches_one_shot(engine):
    """Continuous-batched outputs must equal one-shot generate for the
    same prompts — slot scatter + per-slot cache_len change nothing."""
    toks = jax.random.randint(jax.random.PRNGKey(7), (3, 8), 2,
                              TINY.vocab_size)
    want, _ = engine.generate({"tokens": toks}, n_steps=7)
    sch = Scheduler(n_slots=3)
    for i in range(3):
        sch.submit(np.asarray(toks[i]), 7)
    report = engine.serve(scheduler=sch)
    got = report.outputs
    for i in range(3):
        ref = list(map(int, want[i]))
        # one-shot pads with EOS after termination; continuous drains the
        # slot instead — compare up to the continuous length
        assert got[i] == ref[:len(got[i])]
        assert len(got[i]) <= 7
        if len(got[i]) < 7:              # early drain must be a real EOS
            assert got[i][-1] == engine.ecfg.eos_token


def test_continuous_matches_one_shot_mixed_lengths(engine):
    """Rows at different fill depths decode together bit-exactly."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(2, TINY.vocab_size, size=n).astype(np.int32)
               for n in (4, 9, 6)]
    want = [engine.generate({"tokens": jnp.asarray(p)[None]}, n_steps=5)[0]
            for p in prompts]
    sch = Scheduler(n_slots=3)
    for p in prompts:
        sch.submit(p, 5)
    got = engine.serve(scheduler=sch).outputs
    for i, w in enumerate(want):
        ref = list(map(int, w[0]))
        assert got[i] == ref[:len(got[i])], (i, got[i], ref)


def test_stream_slot_reuse_and_completion():
    """ISSUE acceptance: >=32 mixed-length requests complete through the
    scheduler with slot reuse observed."""
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 EngineConfig(max_len=40, sync_interval=4,
                              prompt_pad_multiple=4))
    rng = np.random.RandomState(0)
    sch = Scheduler(n_slots=4)
    for _ in range(32):
        sch.submit(rng.randint(2, TINY.vocab_size,
                               size=rng.randint(3, 17)),
                   int(rng.randint(2, 10)))
    report = eng.serve(scheduler=sch)
    assert report.stats["drained"] == 32
    assert report.stats["max_slot_reuse"] >= 2        # slots were reused
    assert sum(report.stats["slot_allocations"]) == 32
    for req in report.requests:
        assert 1 <= len(req.tokens) <= req.max_new_tokens
        assert req.admit_step >= req.submit_step
        assert req.finish_step >= req.admit_step


def test_slot_freed_after_eos_budget():
    """A drained slot (budget exhausted) is reallocated to a queued
    request without disturbing the other slot's in-flight decode."""
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, EngineConfig(max_len=48, sync_interval=2))
    rng = np.random.RandomState(4)
    sch = Scheduler(n_slots=1)
    a = sch.submit(rng.randint(2, TINY.vocab_size, size=4), 3)
    b = sch.submit(rng.randint(2, TINY.vocab_size, size=4), 3)
    report = eng.serve(scheduler=sch)
    assert report.stats["slot_allocations"] == [2]    # same slot, twice
    assert report.stats["drained"] == 2
    # second occupant matches its solo run: no bleed-through from the first
    solo, _ = eng.generate({"tokens": jnp.asarray(b.prompt)[None]}, n_steps=3)
    ref = list(map(int, solo[0]))
    assert b.tokens == ref[:len(b.tokens)]


def test_padded_prompt_clamped_to_slot_depth():
    """prompt_pad_multiple rounding must never exceed max_len, and a prompt
    deeper than the slot is rejected with a clear error."""
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 EngineConfig(max_len=30, sync_interval=2,
                              prompt_pad_multiple=8))
    rng = np.random.RandomState(5)
    sch = Scheduler(n_slots=1)
    sch.submit(rng.randint(2, TINY.vocab_size, size=26), 3)  # pads to 30, not 32
    report = eng.serve(scheduler=sch)
    assert report.stats["drained"] == 1
    with pytest.raises(ValueError, match="exceeds the KV slot depth"):
        eng.admit_into_slot(eng.init_pool(1), 0,
                            rng.randint(2, TINY.vocab_size, size=31), 3)


def test_oversized_prompt_rejected_without_aborting_stream(engine):
    """One invalid request must not abort serve() or leak its slot."""
    rng = np.random.RandomState(6)
    sch = Scheduler(n_slots=2)
    ok1 = sch.submit(rng.randint(2, TINY.vocab_size, size=6), 4)
    bad = sch.submit(rng.randint(2, TINY.vocab_size, size=100), 4)  # > max_len
    ok2 = sch.submit(rng.randint(2, TINY.vocab_size, size=6), 4)
    report = engine.serve(scheduler=sch)
    by_rid = {r.rid: r for r in report.requests}
    assert by_rid[bad.rid].status == "rejected"
    assert by_rid[bad.rid].tokens == []
    for req in (ok1, ok2):
        assert by_rid[req.rid].status == "drained"
        assert 1 <= len(by_rid[req.rid].tokens) <= 4


def test_nonpositive_budget_rejected_at_submit():
    sch = Scheduler(n_slots=1)
    with pytest.raises(ValueError, match="max_new_tokens"):
        sch.submit(np.arange(2, 6, dtype=np.int32), 0)


def test_prompt_padding_rejected_for_ssm():
    cfg = dataclasses.replace(TINY, name="tiny-ssm", family="ssm",
                              n_layers=2, ssm_d_state=8, ssm_conv=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="prompt_pad_multiple"):
        Engine(model, params, EngineConfig(max_len=32,
                                           prompt_pad_multiple=8))


# ------------------------------------------------------- serving weights

def test_serving_params_cast_only_leaves_every_use_casts():
    from repro.serve.engine import serving_params
    model = build_model(dataclasses.replace(TINY, qkv_bias=True))
    params = model.init(jax.random.PRNGKey(0))
    cast = serving_params(params)
    flat = jax.tree_util.tree_flatten_with_path(cast)[0]
    dtypes = {jax.tree_util.keystr(p): a.dtype for p, a in flat}
    for name, dt in dtypes.items():
        leaf = name.rsplit("'", 2)[-2]
        want = (jnp.bfloat16 if leaf in ("embed", "unembed", "wq", "wk",
                                         "wv", "wo", "w_gate", "w_up",
                                         "w_down")
                else jnp.float32)
        assert dt == want, (name, dt)
    # values are exactly the cast each use would have made
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(cast)):
        np.testing.assert_array_equal(np.asarray(a.astype(b.dtype)),
                                      np.asarray(b))


def test_engine_tokens_unchanged_by_the_serving_cast():
    """An engine handed bf16 serving weights decodes the same tokens as one
    handed the f32 originals (the cast happens once, in both)."""
    from repro.serve.engine import init_serving_params, serving_params
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 2,
                              TINY.vocab_size)
    ecfg = EngineConfig(max_len=64, eos_token=1)
    a, _ = Engine(model, params, ecfg).generate({"tokens": toks}, n_steps=6)
    b, _ = Engine(model, serving_params(params), ecfg).generate(
        {"tokens": toks}, n_steps=6)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    made = init_serving_params(model, jax.random.PRNGKey(0))
    assert made["tok"]["embed"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(made["tok"]["embed"]),
        np.asarray(params["tok"]["embed"].astype(jnp.bfloat16)))


def test_pool_bytes_beside_leaves_room_for_the_weights():
    from repro.core.target import get_target
    from repro.serve.pool import (POOL_FRACTION, POOL_STEP_COPIES,
                                  pool_bytes_beside)
    params = build_model(TINY).init(jax.random.PRNGKey(0))
    target = get_target("tpu-v5e")
    hbm = target.hierarchy.level("hbm").capacity_bytes
    weights = sum(a.nbytes for a in jax.tree.leaves(params))
    l0, l1 = pool_bytes_beside(params, target=target)
    assert l1 == int(l0 * 0.5)                 # the TPU die-split ratio
    budget = POOL_FRACTION * (hbm - weights)
    assert POOL_STEP_COPIES * l0 + l1 <= budget
    assert POOL_STEP_COPIES * l0 + l1 >= budget - 4
