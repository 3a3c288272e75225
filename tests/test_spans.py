"""The span recorder (``repro.serve.spans``) and what the serve loops and
the scheduler record with it: nesting and parent links, the ring's bound,
the switch, the spans of a paged and a dense boundary in order, request
times beside the step clocks, and no host sync added."""

import jax
import numpy as np
import pytest

from repro.models import build_model
from repro.models.config import ModelConfig
from repro.serve import scheduler as sm
from repro.serve import spans
from repro.serve.engine import Engine, EngineConfig

TINY = ModelConfig(
    name="tiny-spans", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=128,
)
MAX_LEN = 48


@pytest.fixture(scope="module")
def engine():
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    return Engine(model, params, EngineConfig(max_len=MAX_LEN, eos_token=-1,
                                              sync_interval=4))


@pytest.fixture(autouse=True)
def empty_ring():
    spans.clear()
    yield
    spans.clear()


def _geometry():
    pb = sm.kv_bytes_per_token(TINY) * 8
    return sm.derive_page_geometry(TINY, MAX_LEN, page_tokens=8, max_slots=2,
                                   layer0_bytes=pb * 40, layer1_bytes=pb * 8)


def _submit(sch, n=5):
    rng = np.random.RandomState(3)
    for i in range(n):
        sch.submit(rng.randint(2, TINY.vocab_size, size=5 + 3 * i), 3 + 2 * i)
    return sch


def _serve(engine, sch):
    with jax.transfer_guard_device_to_host("disallow"):
        return engine.serve(scheduler=sch)


def _boundaries():
    """Each ``engine.boundary`` span with its children, by start time."""
    rec = spans.recent()
    kids = {}
    for s in rec:
        kids.setdefault(s.parent, []).append(s)
    return [(b, sorted(kids.get(b.id, []), key=lambda s: s.start))
            for b in sorted(rec, key=lambda s: s.start)
            if b.name == "engine.boundary"]


# ------------------------------------------------------------ the recorder
def test_spans_nest_and_link_to_their_parent():
    with spans.span("outer", k=1) as outer:
        with spans.span("first"):
            pass
        with spans.span("second") as second:
            second.args["late"] = 2
    first, got_second, got_outer = spans.recent()
    assert [s.name for s in spans.recent()] == ["first", "second", "outer"]
    assert got_outer is outer and got_second is second
    assert outer.parent == -1
    assert first.parent == second.parent == outer.id
    assert outer.args == {"k": 1} and second.args == {"late": 2}
    assert (outer.start <= first.start <= first.end <= second.start
            <= second.end <= outer.end)
    assert outer.seconds == outer.end - outer.start >= 0


def test_the_ring_keeps_the_newest_spans():
    for i in range(spans.RING + 5):
        with spans.span("s", i=i):
            pass
    rec = spans.recent()
    assert len(rec) == spans.RING
    assert rec[0].args["i"] == 5 and rec[-1].args["i"] == spans.RING + 4


def test_switched_off_records_nothing_but_still_times(monkeypatch, engine):
    monkeypatch.setattr(spans, "enabled", False)
    with spans.span("off") as sp:
        pass
    assert sp.seconds >= 0 and sp.id == -1
    rep = _serve(engine, _submit(sm.Scheduler(n_slots=2, pages=_geometry())))
    assert spans.recent() == []
    assert rep.stats["host_syncs"] == rep.stats["chunks"]
    assert len(rep.stats["boundary_wall_s"]) == rep.stats["chunks"]
    assert all(w > 0 for w in rep.stats["boundary_wall_s"])


# ---------------------------------------------------------- the serve loops
def test_paged_boundary_records_its_phases_in_order(engine):
    sch = _submit(sm.Scheduler(n_slots=2, pages=_geometry()))
    rep = _serve(engine, sch)
    assert rep.stats["host_syncs"] == rep.stats["chunks"]
    bounds = _boundaries()
    assert len(bounds) == rep.stats["chunks"]
    prompt_len = {r.rid: r.prompt_len for r in rep.requests}
    admitted_rids = []
    for b, kids in bounds:
        names = [k.name for k in kids]
        n_pre = len(b.args["admitted"])
        assert names == (["sched.plan"] + ["engine.prefill"] * n_pre
                         + ["engine.upload", "engine.generate",
                            "engine.drain", "engine.emit"])
        assert all(b.start <= k.start <= k.end <= b.end for k in kids)
        for k in kids[1:1 + n_pre]:
            admitted_rids.append(k.args["rid"])
            assert k.args["tokens"] == prompt_len[k.args["rid"]]
        assert b.args["host_syncs"] == 1
        assert b.args["spills"] == b.args["restores"] == 0
        assert b.args["prefill_tokens"] == 0
        assert b.args["emitted"] == n_pre + sum(g for _, g in b.args["decode"])
    assert [b.args["index"] for b, _ in bounds] == list(range(len(bounds)))
    assert sorted(admitted_rids) == sorted(r.rid for r in rep.requests)
    assert rep.stats["boundary_wall_s"] == [b.seconds for b, _ in bounds]
    assert rep.stats["boundary_tokens"] == [b.args["emitted"]
                                            for b, _ in bounds]


def test_decode_args_give_each_live_slot_its_frontier(engine):
    sch = sm.Scheduler(n_slots=2, pages=_geometry())
    for n in (5, 9):
        sch.submit(np.arange(2, 2 + n), 12)
    _serve(engine, sch)
    (b0, _), (b1, _), (b2, _) = _boundaries()
    # boundary 0 admits both; each decodes from the end of its prompt, and
    # its chunk of 4 steps gives 4 tokens beyond the prefill's first
    assert sorted(b0.args["admitted"]) == [5, 9]
    assert sorted(b0.args["decode"]) == [(5, 4), (9, 4)]
    assert sorted(b1.args["decode"]) == [(5 + 4, 4), (9 + 4, 4)]
    assert sorted(b2.args["decode"]) == [(5 + 8, 3), (9 + 8, 3)]


def test_request_times_follow_the_step_clocks(engine):
    rep = _serve(engine, _submit(sm.Scheduler(n_slots=2, pages=_geometry())))
    done = [r for r in rep.requests if r.status == sm.DRAINED]
    assert len(done) == 5
    for r in done:
        assert 0 < r.submit_t <= r.admit_t <= r.first_t <= r.finish_t
        assert r.submit_step <= r.admit_step <= r.first_step <= r.finish_step
    for clock in ("admit", "first", "finish"):
        for a in done:
            for b in done:
                if getattr(a, clock + "_step") < getattr(b, clock + "_step"):
                    assert getattr(a, clock + "_t") < getattr(b, clock + "_t")
    # the first token and a request's last are stamped at the end of the
    # drain that delivered them
    drains = {s.end for s in spans.recent() if s.name == "engine.drain"}
    assert all(r.first_t in drains and r.finish_t in drains for r in done)


def test_dense_loop_records_its_boundaries(engine):
    rep = _serve(engine, _submit(sm.Scheduler(n_slots=2)))
    assert rep.stats["host_syncs"] == rep.stats["chunks"]
    bounds = _boundaries()
    assert len(bounds) == rep.stats["chunks"]
    for b, kids in bounds:
        names = [k.name for k in kids]
        assert names == (["sched.plan"]
                         + ["engine.prefill"] * len(b.args["admitted"])
                         + ["engine.generate", "engine.drain", "engine.emit"])
        assert b.args["host_syncs"] == 1
    done = [r for r in rep.requests if r.status == sm.DRAINED]
    assert all(0 < r.submit_t <= r.admit_t <= r.first_t <= r.finish_t
               for r in done)


def test_phase_timing_charges_each_phase_its_span(engine, monkeypatch):
    monkeypatch.setattr(engine.ecfg, "phase_timing", True)
    rep = engine.serve(scheduler=_submit(sm.Scheduler(n_slots=2,
                                                      pages=_geometry())))
    by_phase = {}
    for s in spans.recent():
        if s.name in ("engine.prefill", "engine.generate", "engine.drain"):
            by_phase[s.name[7:]] = by_phase.get(s.name[7:], 0.0) + s.seconds
    assert rep.stats["phase_s"] == pytest.approx(by_phase, rel=1e-12)
