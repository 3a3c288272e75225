"""Disaggregated prefill/decode roles (DESIGN.md §Disaggregated serving):
the handover primitive's ownership guard, the scheduler's HandoverStep
emission at the final prefill chunk, role-filtered block-table views, the
construction-time gates, and the end-to-end counters/sync discipline of a
disaggregated serve. Bit-identity across the feature matrix lives in
tests/test_equivalence_matrix.py."""

import jax
import numpy as np
import pytest

from repro.models import build_model
from repro.models.config import ModelConfig
from repro.serve import scheduler as sm
from repro.serve.engine import Engine, EngineConfig
from repro.serve.pool import DECODE_ROLE, PREFILL_ROLE, PoolManager

MAX_LEN = 64
PT = 8

TINY = ModelConfig(
    name="tiny-disagg", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=128,
)


def _geometry(cfg, n_layer0=40, n_layer1=64):
    pb = sm.kv_bytes_per_token(cfg) * PT
    return sm.PageGeometry(page_tokens=PT, n_pages=n_layer0 + 1,
                           n_spill_pages=n_layer1 + 1,
                           max_pages_per_slot=-(-MAX_LEN // PT),
                           page_bytes=pb)


# ------------------------------------------------- the handover primitive

def _bare_pools():
    """Ownership bookkeeping touches neither the model nor the pool
    arrays, so a PoolManager with no model is a valid unit-test subject."""
    return PoolManager(None, None, lambda make: make())


def test_transfer_ownership_flips_one_entry():
    pools = _bare_pools()
    pools.claim(3, PREFILL_ROLE)
    pools.transfer_ownership(3, [5, 9, 12])
    assert pools.owner[3] == DECODE_ROLE
    assert (pools.handovers, pools.handover_pages) == (1, 3)
    pools.release(3)
    assert 3 not in pools.owner
    pools.release(3)                       # idempotent


def test_transfer_ownership_guards_src():
    """A handover for a slot the prefill role does not own (never claimed,
    preempted away, or already handed over) must refuse loudly — silent
    acceptance would corrupt role routing."""
    pools = _bare_pools()
    with pytest.raises(RuntimeError, match="owned by None"):
        pools.transfer_ownership(0, [1])
    pools.claim(0, PREFILL_ROLE)
    pools.transfer_ownership(0, [1])
    with pytest.raises(RuntimeError, match="owned by 'decode'"):
        pools.transfer_ownership(0, [1])   # double handover
    assert (pools.handovers, pools.handover_pages) == (1, 1)


def test_released_slot_cannot_hand_over():
    """Preemption frees the slot and its owner entry with it — a stale
    handover planned against a released slot must refuse; the restore
    re-claims under whatever role the request is in by then."""
    pools = _bare_pools()
    pools.claim(2, PREFILL_ROLE)
    pools.release(2)
    with pytest.raises(RuntimeError):
        pools.transfer_ownership(2, [4])


# ------------------------------------- scheduler: HandoverStep emission

def test_handover_at_final_chunk():
    """A chunked prompt hands over exactly once, at the boundary that
    plans its final chunk; earlier boundaries keep prefill ownership."""
    sch = sm.Scheduler(2, pages=_geometry(TINY), disaggregate=True,
                       chunk_prefill_tokens=6)
    req = sch.submit(np.arange(2, 16, dtype=np.int32), 4)   # 14 tokens
    plan1 = sch.plan_boundary(chunk_tokens=4, max_len=MAX_LEN)
    assert [s.final for s in plan1.prefill_steps] == [False]
    assert plan1.handovers == [] and req.owner == PREFILL_ROLE
    plan2 = sch.plan_boundary(chunk_tokens=4, max_len=MAX_LEN)
    assert plan2.handovers == [] and req.owner == PREFILL_ROLE
    plan3 = sch.plan_boundary(chunk_tokens=4, max_len=MAX_LEN)
    assert [s.final for s in plan3.prefill_steps] == [True]
    (h,) = plan3.handovers
    assert (h.slot, h.req) == (0, req)
    assert h.pages == list(req.pages) and h.pages
    assert req.owner == DECODE_ROLE
    assert (sch.handovers, sch.handover_pages) == (1, len(req.pages))


def test_handover_immediate_when_unchunked():
    """Whole-prompt admission completes prefill within its boundary, so
    the handover rides the same plan."""
    sch = sm.Scheduler(2, pages=_geometry(TINY), disaggregate=True)
    req = sch.submit(np.arange(2, 16, dtype=np.int32), 4)
    plan = sch.plan_boundary(chunk_tokens=4, max_len=MAX_LEN)
    assert [h.req for h in plan.handovers] == [req]
    assert req.owner == DECODE_ROLE


def test_block_table_role_views():
    """The decode view carries a slot's row exactly from its handover on;
    before that the row lives only in the prefill view (junk decode writes
    for mid-prefill slots route to null page 0)."""
    sch = sm.Scheduler(2, pages=_geometry(TINY), disaggregate=True,
                       chunk_prefill_tokens=6)
    sch.submit(np.arange(2, 16, dtype=np.int32), 4)
    sch.plan_boundary(chunk_tokens=4, max_len=MAX_LEN)
    full = sch.block_table()
    assert full[0].any()
    assert sch.block_table(role=PREFILL_ROLE)[0].tolist() == full[0].tolist()
    assert not sch.block_table(role=DECODE_ROLE)[0].any()
    sch.plan_boundary(chunk_tokens=4, max_len=MAX_LEN)
    sch.plan_boundary(chunk_tokens=4, max_len=MAX_LEN)      # final chunk
    full = sch.block_table()
    assert sch.block_table(role=DECODE_ROLE)[0].tolist() == full[0].tolist()
    assert not sch.block_table(role=PREFILL_ROLE)[0].any()


# ------------------------------------------------- construction-time gates

def test_disaggregate_requires_pages():
    with pytest.raises(ValueError, match="paged pool"):
        sm.Scheduler(2, disaggregate=True)
    sch = sm.Scheduler(2)
    with pytest.raises(ValueError, match="paged pool"):
        sch.enable_disaggregation()


def test_enable_disaggregation_must_precede_admission():
    sch = sm.Scheduler(2, pages=_geometry(TINY), chunk_prefill_tokens=6)
    sch.submit(np.arange(2, 10, dtype=np.int32), 4)
    sch.plan_boundary(chunk_tokens=4, max_len=MAX_LEN)
    with pytest.raises(RuntimeError, match="precede the first admission"):
        sch.enable_disaggregation()


def test_engine_rejects_disagg_on_dense_pool():
    model = build_model(TINY)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)),
                 EngineConfig(max_len=MAX_LEN, sync_interval=4,
                              disaggregate=True))
    sch = sm.Scheduler(2)                  # dense slot-slab, no pages
    sch.submit(np.arange(2, 10, dtype=np.int32), 4)
    with pytest.raises(ValueError, match="paged pool"):
        eng.serve(scheduler=sch)


# --------------------------------------------------- end-to-end discipline

@pytest.fixture(scope="module")
def engine():
    model = build_model(TINY)
    return Engine(model, model.init(jax.random.PRNGKey(0)),
                  EngineConfig(max_len=MAX_LEN, sync_interval=4))


def _requests(n=5, seed=3):
    rng = np.random.RandomState(seed)
    reqs = [(rng.randint(2, 128, size=int(rng.randint(4, 20))
                         ).astype(np.int32), int(rng.randint(3, 8)))
            for _ in range(n)]
    reqs.append((rng.randint(2, 128, size=40).astype(np.int32), 5))
    return reqs


def test_disagg_serve_counters_and_sync_discipline(engine):
    """One disaggregated serve: every prompt hands over exactly once,
    pool-manager and scheduler counters agree, ownership drains with the
    slots, and the per-role sync budget holds — the decode role reads one
    fetch per boundary, the prefill role only at boundaries that completed
    a prompt (all under the transfer guard)."""
    reqs = _requests()
    sch = sm.Scheduler(3, pages=_geometry(TINY), disaggregate=True,
                       chunk_prefill_tokens=8)
    rids = [sch.submit(p, g).rid for p, g in reqs]
    with jax.transfer_guard_device_to_host("disallow"):
        rep = engine.serve(scheduler=sch)

    st = rep.stats
    assert st["disaggregate"] is True
    assert st["handovers"] == len(reqs)
    assert st["handover_pages"] > 0
    assert (engine.pools.handovers, engine.pools.handover_pages) == \
        (st["handovers"], st["handover_pages"])
    assert engine.pools.owner == {}        # all slots drained and released
    by_role = st["host_syncs_by_role"]
    assert by_role[DECODE_ROLE] == st["chunks"]
    assert 0 < by_role[PREFILL_ROLE] <= st["chunks"]
    assert st["host_syncs"] == by_role[DECODE_ROLE] + by_role[PREFILL_ROLE]
    assert st["decode_tokens"] > 0
    assert len(st["boundary_decode_wall_s"]) == st["chunks"]
    assert all(len(rep.outputs[r]) > 0 for r in rids)


def test_disagg_matches_combined(engine):
    """The role split moves no bits: same engine, same requests, with and
    without disaggregation — bit-identical outputs."""
    reqs = _requests(seed=9)
    outs = {}
    for disagg in (False, True):
        sch = sm.Scheduler(3, pages=_geometry(TINY), disaggregate=disagg,
                           chunk_prefill_tokens=8)
        rids = [sch.submit(p, g).rid for p, g in reqs]
        with jax.transfer_guard_device_to_host("disallow"):
            rep = engine.serve(scheduler=sch)
        outs[disagg] = [rep.outputs[r] for r in rids]
    assert outs[True] == outs[False]


def test_engine_config_flag_enables_routing(engine):
    """EngineConfig(disaggregate=True) must route a plain paged scheduler
    through enable_disaggregation() — no silent combined fallback."""
    prev = engine.ecfg.disaggregate
    engine.ecfg.disaggregate = True
    try:
        sch = sm.Scheduler(3, pages=_geometry(TINY),
                           chunk_prefill_tokens=8)
        sch.submit(np.arange(2, 20, dtype=np.int32), 4)
        with jax.transfer_guard_device_to_host("disallow"):
            rep = engine.serve(scheduler=sch)
    finally:
        engine.ecfg.disaggregate = prev
    assert sch.disaggregate is True
    assert rep.stats["handovers"] == 1
