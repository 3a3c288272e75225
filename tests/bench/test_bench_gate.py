"""The arrival gate on a hand-built schedule: admission by due time,
token stamps, and the metrics read from them; and the backlog top-up."""

from __future__ import annotations

import numpy as np
import pytest

from bench.gate import Gate
from bench.readings import Readings
from bench.traffic import Arrival
from repro.serve.scheduler import DECODING, PageGeometry

GEOM = PageGeometry(page_tokens=16, n_pages=65, n_spill_pages=9,
                    max_pages_per_slot=4, page_bytes=1024)
SHAPE = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def sleep(self, s: float) -> None:
        self.t += s


def drive(gate: Gate, clock: Clock, chunk: int = 2, step_s: float = 1.0):
    """The engine's paged serve loop, reduced to what the gate sees: each
    boundary takes ``step_s``; an admitted request gets its first token
    and the decode chunk's tokens at the drain that follows."""
    while gate.has_work():
        plan = gate.plan_boundary(chunk_tokens=chunk, max_len=64)
        fresh = {req.rid for _, req in plan.admits}
        for _, req in plan.admits:
            req.status = DECODING
        clock.t += step_s
        for slot in sorted(gate.active):
            req = gate.active[slot]
            if req.rid in fresh:
                req.tokens.append(7)
            room = req.max_new_tokens - len(req.tokens)
            req.tokens.extend([7] * min(chunk, room))
            if len(req.tokens) >= req.max_new_tokens:
                gate.complete(slot)


def schedule(*items):
    for due, plen, new in items:
        yield Arrival(due_s=due, prompt=np.full((plen,), 3, np.int32),
                      max_new_tokens=new)


def readings(gate: Gate, seconds: float) -> Readings:
    return Readings(gate=gate, seconds=seconds, shape=SHAPE, n_slots=4,
                    sync_interval=2, chips=1, kind="TPU v5 lite")


def test_gate_admits_by_schedule_and_stamps_tokens():
    clock = Clock()
    gate = Gate(n_slots=4, pages=GEOM,
                arrivals=schedule((0.5, 10, 3), (0.5, 20, 5), (2.5, 12, 1),
                                  (100.0, 8, 1)),
                warm_in_s=0.0, seconds=10.0, drain_cap_s=5.0,
                clock=clock, sleep=clock.sleep)
    drive(gate, clock)
    recs = [gate.records[i] for i in range(3)]
    # two due at 0.5: the gate slept until then and admitted both
    assert [r.admit_t for r in recs] == [0.5, 0.5, 2.5]
    assert recs[0].deliveries == [(1.5, 3)]
    assert recs[1].deliveries == [(1.5, 3), (2.5, 2)]
    assert recs[2].deliveries == [(3.5, 1)]
    assert gate.wait_s == pytest.approx(0.5 + 96.5)
    r = readings(gate, 10.0)
    assert r.ttfts_s() == pytest.approx([1.0, 1.0, 1.0])
    # the 2 tokens of request 1's second drain: 1 s apart, 2 tokens
    assert r.itls_s() == pytest.approx([0.5, 0.5])
    assert r.end_to_end("ttft_p90_ms") == pytest.approx(1000.0)
    assert r.end_to_end("itl_p95_ms") == pytest.approx(500.0)
    assert r.output_tokens() == 9
    assert r.attempted_failed() == (3, 0)
    assert r.queue_wait_p90_ms() == pytest.approx(0.0)
    # decode spans: (frontier at chunk start, tokens the chunk produced)
    b = gate.boundaries
    assert b[0].admitted == [10, 20] and b[0].decode == [(10, 2), (20, 2)]
    assert b[1].decode == [(22, 2)]
    assert b[2].admitted == [12] and b[2].decode == [(12, 0)]


def test_gate_counts_a_request_without_first_token_as_failed():
    clock = Clock()
    # one slot, and the first request holds it past the drain cap
    gate = Gate(n_slots=1, pages=GEOM,
                arrivals=schedule((0.0, 10, 40), (1.0, 10, 2), (100.0, 8, 1)),
                warm_in_s=0.0, seconds=4.0, drain_cap_s=2.0,
                clock=clock, sleep=clock.sleep)
    drive(gate, clock, chunk=1)
    r = readings(gate, 4.0)
    assert r.attempted_failed() == (2, 1)
    # the failed one counts at the cap: close 4 + cap 2 - due 1
    assert max(r.ttfts_s()) == pytest.approx(5.0)


def test_backlog_keeps_n_slots_queued_at_every_boundary():
    clock = Clock()
    gate = Gate(n_slots=4, pages=GEOM, arrivals=schedule(
        *[(0.0, 10 + i, 3) for i in range(200)]),
        warm_in_s=0.0, seconds=6.0, backlog=True,
        clock=clock, sleep=clock.sleep)
    gate.has_work()
    for _ in range(6):
        plan = gate.plan_boundary(chunk_tokens=2, max_len=64)
        assert len(gate.queue) + len(plan.admits) >= gate.n_slots
        for _, req in plan.admits:
            req.status = DECODING
        clock.t += 1.0
        for slot in sorted(gate.active):
            req = gate.active[slot]
            req.tokens.extend([7, 7])
            if len(req.tokens) >= req.max_new_tokens:
                gate.complete(slot)
        gate.has_work()
    assert len(gate.active) + len(gate.queue) >= 4
    r = readings(gate, 6.0)
    assert r.output_tokens() > 0


def test_a_request_due_after_the_last_boundary_in_the_window_counts():
    """Due at 9.5 s, between the boundary planned at 9 s and the close at
    10 s, while another request keeps the engine busy: the run goes on
    past the close to admit and serve it."""
    clock = Clock()
    gate = Gate(n_slots=4, pages=GEOM,
                arrivals=schedule((0.0, 10, 40), (9.5, 10, 2),
                                  (100.0, 8, 1)),
                warm_in_s=0.0, seconds=10.0, drain_cap_s=5.0,
                clock=clock, sleep=clock.sleep)
    drive(gate, clock)
    r = readings(gate, 10.0)
    assert r.attempted_failed() == (2, 0)
    assert r.ttfts_s() == pytest.approx([1.0, 1.5])
