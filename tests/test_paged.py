"""Paged two-tier KV pool: allocator invariants, scheduler consistency,
paged == dense bit-exact equivalence, and the page-walk kernel."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import build_model
from repro.models.config import ModelConfig
from repro.serve import scheduler as sm
from repro.serve.engine import Engine, EngineConfig

TINY = ModelConfig(
    name="tiny-paged", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=128,
)

TINY_WINDOW = dataclasses.replace(
    TINY, name="tiny-window", n_layers=3, window=8, local_global_ratio=2)

TINY_MLA = dataclasses.replace(
    TINY, name="tiny-mla", n_kv_heads=4, use_mla=True, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)

TINY_HYBRID = dataclasses.replace(
    TINY, name="tiny-hybrid", family="hybrid", n_layers=4,
    ssm_d_state=8, ssm_conv=4, attn_period=2, attn_offset=1)


def _tight_geometry(cfg, max_len=32, page_tokens=8, n_layer0=6, n_layer1=8):
    pb = sm.kv_bytes_per_token(cfg) * page_tokens
    return sm.derive_page_geometry(
        cfg, max_len, page_tokens=page_tokens, max_slots=3,
        layer0_bytes=pb * n_layer0, layer1_bytes=pb * n_layer1)


# ------------------------------------------------------------ page pool

def test_page_pool_alloc_free_roundtrip():
    pool = sm.PagePool(6)
    a = pool.alloc(3)
    b = pool.alloc(2)
    assert set(a).isdisjoint(b)
    assert 0 not in a + b                    # null page never handed out
    assert pool.alloc(1) is None             # exhausted: all-or-nothing
    assert pool.in_use == 5 and pool.high_water == 5
    pool.free(a)
    assert pool.alloc(4) is None             # only 3 free: no partial grant
    c = pool.alloc(3)
    assert set(c) == set(a)


def test_page_pool_rejects_double_free_and_foreign_pages():
    pool = sm.PagePool(4)
    a = pool.alloc(2)
    pool.free(a)
    with pytest.raises(RuntimeError, match="double free"):
        pool.free([a[0]])
    with pytest.raises(ValueError, match="outside"):
        pool.free([99])
    with pytest.raises(ValueError, match="outside"):
        pool.free([0])                       # the null page is not poolable


def test_geometry_rejects_undersized_layer0():
    with pytest.raises(ValueError, match="layer-0 budget"):
        sm.derive_page_geometry(TINY, 64, page_tokens=8,
                                layer0_bytes=sm.kv_bytes_per_token(TINY) * 8)


def test_derive_n_slots_paged_beats_dense_in_same_budget():
    """The capacity win: inside the SAME layer-0 byte budget, the paged
    pool carries >= 1.3x the dense pool's concurrent slots."""
    max_len = 28
    dense_slots = 3
    budget = dense_slots * sm.kv_bytes_per_token(TINY) * max_len
    geom = sm.derive_page_geometry(TINY, max_len, page_tokens=8,
                                   max_slots=32, layer0_bytes=budget)
    paged_slots = sm.derive_n_slots(TINY, max_len, pages=geom, max_slots=32)
    assert geom.layer0_bytes <= budget
    assert paged_slots >= 1.3 * dense_slots


# Hypothesis property tests for the allocator live in
# tests/test_paged_properties.py (whole-module importorskip, like
# test_properties.py) so these tests still run without hypothesis.

# ------------------------------------------- paged == dense equivalence

@pytest.mark.parametrize("cfg", [TINY_WINDOW, TINY_MLA, TINY_HYBRID],
                         ids=lambda c: c.name)
def test_paged_matches_dense_bit_exact(cfg):
    """Same stream through the dense slot-slab pool and the paged two-tier
    pool (sized to force preemption + spill): outputs must be IDENTICAL,
    under the drain-boundary transfer-guard discipline."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 EngineConfig(max_len=32, eos_token=1, sync_interval=4))
    rng = np.random.RandomState(1)
    prompts = [rng.randint(2, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 9, 4, 7)]
    dense_sch = sm.Scheduler(n_slots=3)
    for p in prompts:
        dense_sch.submit(p, 20)
    dense = eng.serve(scheduler=dense_sch)
    paged_sch = sm.Scheduler(n_slots=3, pages=_tight_geometry(cfg))
    for p in prompts:
        paged_sch.submit(p, 20)
    with jax.transfer_guard_device_to_host("disallow"):
        paged = eng.serve(scheduler=paged_sch)
    assert {r.rid: r.tokens for r in paged.requests} == \
        {r.rid: r.tokens for r in dense.requests}
    # the tight layer-0 budget must actually exercise the spill tier
    assert paged.stats["preemptions"] >= 1
    assert paged.stats["restores"] >= 1
    assert paged.stats["spill_high_water"] >= 1
    assert paged.stats["host_syncs"] == paged.stats["chunks"]


def test_paged_matches_one_shot_generate():
    """Paged continuous batching == one-shot generate for the same prompts
    (ISSUE acceptance: same transfer-guard discipline as PR 2)."""
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 EngineConfig(max_len=32, eos_token=1, sync_interval=4))
    toks = jax.random.randint(jax.random.PRNGKey(7), (3, 8), 2,
                              TINY.vocab_size)
    want, _ = eng.generate({"tokens": toks}, n_steps=7)
    sch = sm.Scheduler(n_slots=3, pages=_tight_geometry(TINY))
    for i in range(3):
        sch.submit(np.asarray(toks[i]), 7)
    with jax.transfer_guard_device_to_host("disallow"):
        got = eng.serve(scheduler=sch).outputs
    for i in range(3):
        ref = list(map(int, want[i]))
        assert got[i] == ref[:len(got[i])]
        assert len(got[i]) <= 7
        if len(got[i]) < 7:
            assert got[i][-1] == eng.ecfg.eos_token


def test_paged_stream_reuse_and_rejection():
    """32 mixed requests (including an oversized one) drain through a tiny
    paged pool with page reuse; the bad request is rejected cleanly."""
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 EngineConfig(max_len=32, eos_token=1, sync_interval=4))
    rng = np.random.RandomState(0)
    sch = sm.Scheduler(n_slots=3, pages=_tight_geometry(TINY))
    bad = sch.submit(rng.randint(2, 128, size=100), 4)    # > max_len
    for _ in range(32):
        sch.submit(rng.randint(2, 128, size=rng.randint(3, 17)),
                   int(rng.randint(2, 10)))
    report = eng.serve(scheduler=sch)
    assert report.stats["drained"] == 32
    assert report.stats["rejected"] == 1
    by_rid = {r.rid: r for r in report.requests}
    assert by_rid[bad.rid].status == sm.REJECTED
    assert by_rid[bad.rid].tokens == []
    assert report.stats["pages_in_use"] == 0              # all pages freed
    assert report.stats["pages_high_water"] >= 3
    for req in report.requests:
        if req.status == sm.DRAINED:
            assert 1 <= len(req.tokens) <= req.max_new_tokens


# ------------------------------------------------------ page-walk kernel

@pytest.mark.parametrize("window", [None, "straddle"])
@pytest.mark.parametrize("partial_block", [False, True])
@pytest.mark.parametrize("page_tokens", [8, 16])
def test_paged_flash_decode_matches_oracle(page_tokens, partial_block,
                                           window):
    """The Pallas page-walk kernel (interpret mode on CPU) == the
    dense-masked oracle over each slot's contiguous view, within
    online-softmax tolerance. The slots' frontiers sit at 0, either side of
    and on the first block edge, and on the last position; pages past a
    frontier map to null page 0 (finite junk); two slots alias one physical
    page; ``P_max`` is whole blocks, or has a partial block at its end; a
    window reaches back across a block edge."""
    from repro.kernels.paged_attention import (decode_attention_masked,
                                               paged_decode_attention,
                                               walk_pages_per_block)
    rng = np.random.RandomState(page_tokens + 2 * partial_block)
    hq, hkv, d, pt = 4, 2, 32, page_tokens
    ppb = walk_pages_per_block(pt, 10**6)
    p_max = 2 * ppb + (3 if partial_block else 0)
    assert walk_pages_per_block(pt, p_max) == ppb
    bk = ppb * pt
    cache_len = np.asarray([0, bk - 1, bk, bk + 1, p_max * pt - 1], np.int32)
    # at frontier bk + 1 the window reaches back from block 1 into block 0
    win = None if window is None else 3 * pt + 5
    b = len(cache_len)
    bt = np.zeros((b, p_max), np.int32)
    n_pages = 1
    for i, f in enumerate(cache_len):
        for p in range(f // pt + 1):
            bt[i, p], n_pages = n_pages, n_pages + 1
    bt[2, 0] = bt[1, 0]                        # aliased prefix page
    kp = rng.randn(n_pages, hkv, pt, d).astype(np.float32)
    vp = rng.randn(n_pages, hkv, pt, d).astype(np.float32)
    kp[0] = vp[0] = 1e3                        # null-page junk
    k = np.concatenate([kp[bt[:, p]] for p in range(p_max)], axis=2)
    v = np.concatenate([vp[bt[:, p]] for p in range(p_max)], axis=2)
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    cache_len = jnp.asarray(cache_len)
    want = decode_attention_masked(q, jnp.asarray(k), jnp.asarray(v),
                                   cache_len, window=win)
    got = paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                 jnp.asarray(bt), cache_len, window=win,
                                 impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------- preemption policy edges

def test_growth_self_spills_instead_of_evicting_older_resident():
    """When the grower is itself the youngest resident, IT spills — an
    older sequence is never sacrificed for a younger one."""
    geom = _tight_geometry(TINY, max_len=32, page_tokens=8,
                           n_layer0=6, n_layer1=8)
    sch = sm.Scheduler(n_slots=2, pages=geom)
    a = sch.submit(np.arange(2, 22, dtype=np.int32), 12)   # 20-tok prompt
    b = sch.submit(np.arange(2, 6, dtype=np.int32), 24)    # 4-tok prompt
    plan = sch.plan_boundary(chunk_tokens=8, max_len=32)
    assert [r.rid for _, r in plan.admits] == [a.rid, b.rid]
    assert sch.page_pool.n_free == 0          # 4 + 2 pages: layer 0 full
    a.tokens.extend([7] * 8)                  # simulate one decode chunk
    b.tokens.extend([7] * 8)
    plan = sch.plan_boundary(chunk_tokens=8, max_len=32)
    # A (older, fully grown) keeps its pages; B (younger) needed one more
    # page and self-spilled rather than evicting A
    assert [act.req.rid for act in plan.spills] == [b.rid]
    assert b.status == sm.PREEMPTED and b.preemptions == 1
    assert a.rid in {r.rid for r in sch.active.values()}
    # drain A -> B restores with its full need and finishes
    while sch.has_work():
        for slot in sorted(sch.active):
            req = sch.active[slot]
            take = min(8, req.max_new_tokens - len(req.tokens),
                       32 - req.cache_len)
            req.tokens.extend([7] * max(take, 0))
            if len(req.tokens) >= req.max_new_tokens or req.cache_len >= 32:
                sch.complete(slot)
        if sch.has_work():
            sch.plan_boundary(chunk_tokens=8, max_len=32)
    assert b.status == sm.DRAINED and sch.restores == 1
    assert a.preemptions == 0                 # the oldest never spilled


def test_spill_tier_exhaustion_leaves_scheduler_consistent():
    """A failed preemption (layer 1 full) must not orphan the victim or
    leak its pages: allocation is checked before any bookkeeping."""
    geom = _tight_geometry(TINY, max_len=32, page_tokens=8,
                           n_layer0=4, n_layer1=1)     # 1 spill page only
    sch = sm.Scheduler(n_slots=2, pages=geom)
    sch.submit(np.arange(2, 8, dtype=np.int32), 20)    # 6-tok: 2 pages
    sch.submit(np.arange(2, 8, dtype=np.int32), 20)
    sch.plan_boundary(chunk_tokens=8, max_len=32)      # both admitted: 4/4
    for req in sch.active.values():
        req.tokens.extend([7] * 8)
    with pytest.raises(RuntimeError, match="spill tier exhausted"):
        sch.plan_boundary(chunk_tokens=8, max_len=32)  # 2-page victim > 1
    # victim untouched: still active, pages conserved, nothing leaked
    assert len(sch.active) == 2
    active_pages = [p for r in sch.active.values() for p in r.pages]
    assert sorted(active_pages) == [1, 2, 3, 4]
    assert sch.spill_pool.in_use == 0 and sch.seat_pool.in_use == 0
