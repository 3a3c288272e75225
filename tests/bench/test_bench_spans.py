"""The program's spans read for the benchmark (``bench/spans.py``): the
three per-layer metrics they give, in the cells that list them and no
others; readings from a program without the recorder; the clock mapping
and the attribution of idle gaps to the innermost span, on hand-built
spans and on spans recorded in a traced chip run."""

from __future__ import annotations

import json
import math
import types
from pathlib import Path

import pytest

import bench.run as run_mod
from bench import spans as bspans
from bench import trace as trace_mod
from bench.spans import Rec
from bench.trace import Op, Trace
from bench_helpers import cpu_device

TESTDATA = Path(__file__).resolve().parents[2] / "bench" / "testdata"
NEW = {"boundary_ms_p95", "admit_to_first_ms_p90", "sched_queue_ms_p90"}


def traced_run(root, workload, capsys) -> dict:
    rc = run_mod.main(["--workload", workload, "--seed", "3000000029",
                       "--seconds", "2", "--trace", "1"], root=root,
                      check_device=cpu_device, cache_dir=None)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,want", [
    ("qwen2.5-3b.chat", {"boundary_ms_p95"}),
    ("qwen2.5-3b.code", NEW)])
def test_a_traced_run_reports_the_span_metrics_its_cell_lists(
        workload, want, tiny_root, capsys):
    res = traced_run(tiny_root, workload, capsys)
    assert res["correct"] is True
    assert set(res["metrics"]) & NEW == want
    for name in want:
        value = res["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0
        assert res["metrics"][name]["unit"] == "ms"


def _window_run(requests, records, t_open=10.0, t_close=20.0):
    gate = types.SimpleNamespace(t_open=t_open, t_close=t_close,
                                 records=records, requests=requests)
    return types.SimpleNamespace(gate=gate)


def test_request_readings_take_requests_due_in_the_window():
    due = {1: 9.0, 2: 11.0, 3: 12.0, 4: 19.5, 5: 21.0}
    stamps = {1: (9.5, 10.0, 11.0), 2: (11.0, 11.5, 13.0),
              3: (12.0, 14.0, 14.25), 4: (19.5, 19.75, -1.0),
              5: (21.0, 21.0, 22.0)}
    requests = {rid: types.SimpleNamespace(submit_t=s, admit_t=a, first_t=f)
                for rid, (s, a, f) in stamps.items()}
    records = {rid: types.SimpleNamespace(due=d) for rid, d in due.items()}
    r = _window_run(requests, records)
    # due in the window: 2, 3, 4; queue waits 0.5, 2.0, 0.25 s, and the p90
    # of three lies 0.8 of the way from the second to the third
    assert bspans.sched_queue_ms_p90(r) == pytest.approx(
        1e3 * (0.5 + 0.8 * (2.0 - 0.5)))
    # 4 has no first token yet: admission to first token of 2 and 3
    assert bspans.admit_to_first_ms_p90(r) == pytest.approx(
        1e3 * (0.25 + 0.9 * (1.5 - 0.25)))


def test_a_program_without_the_recorder_reads_nothing(monkeypatch):
    """What the parent of the recorder gives: no ring, no request times."""
    monkeypatch.setattr(bspans, "recorder", lambda: None)
    requests = {1: types.SimpleNamespace()}
    records = {1: types.SimpleNamespace(due=11.0)}
    r = _window_run(requests, records)
    assert bspans.boundary_ms_p95(r) is None
    assert bspans.admit_to_first_ms_p90(r) is None
    assert bspans.sched_queue_ms_p90(r) is None


def test_boundary_time_runs_from_the_plan_to_the_last_drain():
    recorded = [
        Rec("sched.plan", 1.0, 1.1, 1, 0, {}),
        Rec("engine.drain", 1.5, 2.0, 2, 0, {}),
        Rec("engine.drain", 2.0, 2.3, 3, 0, {}),      # a second role's
        Rec("engine.boundary", 1.0, 2.4, 0, -1, {}),
        Rec("sched.plan", 3.0, 3.2, 5, 4, {}),        # started at the close
        Rec("engine.drain", 3.5, 4.0, 6, 4, {}),
        Rec("engine.boundary", 3.0, 4.1, 4, -1, {}),
        Rec("sched.plan", 0.5, 0.6, 7, -1, {}),       # outside any boundary
    ]
    assert bspans.boundary_s(recorded, 0.0, 3.0) == [pytest.approx(1.2)]


def test_innermost_names_each_piece_by_the_span_that_started_last():
    pieces = bspans.innermost([(0, 10, "boundary"), (1, 3, "plan"),
                               (1, 2, "inner"), (5, 6, "drain"),
                               (12, 13, "alone")])
    assert pieces == [(0, 1, "boundary"), (1, 2, "inner"), (2, 3, "plan"),
                      (3, 5, "boundary"), (5, 6, "drain"),
                      (6, 10, "boundary"), (12, 13, "alone")]


def hand_built():
    """Recorder times are trace times plus 100 s; the tracer read its stop
    time 1 us before the window closed."""
    tr = Trace(ops=[[Op("fusion", 0.0, 1.0), Op("fusion", 2.0, 3.0),
                     Op("fusion", 3.0, 5.0), Op("fusion", 6.0, 9.0)]],
               modules=[[]],
               host=[Op(trace_mod.WINDOW_SPAN, 0.0, 10.0),
                     Op("gate.plan", 1.2, 1.5)],
               window=(0.0, 10.0))
    recorded = [Rec("engine.boundary", 101.0, 105.5, 0, -1, {}),
                Rec("sched.plan", 101.3, 101.4, 1, 0, {}),
                Rec("engine.drain", 104.0, 105.2, 2, 0, {}),
                Rec("engine.emit", 105.2, 105.4, 3, 0, {}),
                Rec("engine.boundary", 50.0, 51.0, 4, -1, {})]  # before
    return tr, recorded, 100.0, 110.0 - 1e-6


def test_idle_gaps_go_to_the_innermost_span_on_the_traces_clock():
    tr, recorded, t_start, t_stop = hand_built()
    offset, drift = bspans.clock(tr, t_start, t_stop)
    assert offset == pytest.approx(-100.0)
    assert drift == pytest.approx(1e-6)
    idle = bspans.idle_by_span(tr, recorded, offset)
    # gaps 1-2, 5-6 and 9-10; in 1-2 the plan (1.3-1.4) lies in the gate's
    # span (1.2-1.5), which lies in the boundary
    assert idle == pytest.approx({
        "engine.boundary": 0.2 + 0.5 + 0.1, "gate.plan": 0.2,
        "sched.plan": 0.1, "engine.drain": 0.2, "engine.emit": 0.2,
        bspans.UNOWNED: 0.5 + 1.0})
    a = bspans.attribution(tr, recorded, t_start, t_stop)
    assert a["idle_s"] == pytest.approx(3.0)
    assert a["named_share"] == pytest.approx(0.5)
    assert list(a["idle_by_span"])[0] == bspans.UNOWNED


def test_a_recording_round_trips(tmp_path):
    tr, recorded, t_start, t_stop = hand_built()
    path = tmp_path / "rec.json.gz"
    bspans.save(path, tr, recorded, t_start, t_stop)
    tr2, rec2, t0, t1 = bspans.load(path)
    assert (t0, t1) == (t_start, t_stop)
    assert rec2 == recorded[:4]         # the span before the window is cut
    assert tr2.window == tr.window and tr2.host == tr.host
    assert [(o.name, o.start, o.end) for o in tr2.ops[0]] == \
        [(o.name, o.start, o.end) for o in tr.ops[0]]


def test_recorded_v5e_code_run():
    """The traced window of a run of the code cell on one TPU v5e (seed
    2900013101): four boundaries, 16 admissions. On the trace's clock the
    spans hold the device work they dispatched, and named spans own the
    idle time."""
    tr, recorded, t_start, t_stop = bspans.load(
        TESTDATA / "v5e_code_spans.json.gz")
    offset, drift = bspans.clock(tr, t_start, t_stop)
    assert abs(drift) < 0.5e-3
    by_id = {s.id: s for s in recorded}
    bounds = [s for s in recorded if s.name == "engine.boundary"]
    assert len(bounds) == 4
    prefills = [s for s in recorded if s.name == "engine.prefill"]
    assert len(prefills) == sum(len(b.args["admitted"]) for b in bounds)
    drain = {s.parent: s for s in recorded if s.name == "engine.drain"}
    generate = {s.parent: s for s in recorded if s.name == "engine.generate"}

    def inside(ops, spans_from):
        ivs = [(s.start + offset, drain[s.parent].end + offset)
               for s in spans_from]
        return all(any(a <= o.start and o.end <= b for a, b in ivs)
                   for o in ops)

    # each decode chunk's page walk runs between its dispatch and the end
    # of its drain, each admission's flash between its prefill's dispatch
    # and the drain that reads its first token
    walk = [o for o in tr.ops[0] if o.name == "paged_flash_decode"]
    flash = [o for o in tr.ops[0] if o.name == "flash_attention"]
    assert len(walk) == 4 * 8 * 36 and len(flash) == 16 * 36
    assert inside(walk, [generate[b.id] for b in bounds])
    assert inside(flash, prefills)
    assert all(by_id[p.parent].name == "engine.boundary" for p in prefills)
    a = bspans.attribution(tr, recorded, t_start, t_stop)
    assert a["idle_s"] == pytest.approx(
        trace_mod.window_s(tr) - trace_mod.busy_s(tr), abs=1e-9)
    assert a["named_share"] >= 0.95
    assert max(a["idle_by_span"], key=a["idle_by_span"].get) == \
        "engine.drain"


def test_time_to_first_token_splits_into_gate_scheduler_and_engine():
    requests = {1: types.SimpleNamespace(submit_t=11.5, admit_t=12.0,
                                         first_t=14.0),
                2: types.SimpleNamespace(submit_t=12.0, admit_t=12.0,
                                         first_t=-1.0)}
    records = {1: types.SimpleNamespace(due=11.0),
               2: types.SimpleNamespace(due=11.5)}
    split = bspans.ttft_split(_window_run(requests, records))
    assert {k: v["p90"] for k, v in split.items()} == pytest.approx(
        {"gate": 500.0, "scheduler": 500.0, "engine": 2000.0,
         "ttft": 3000.0})
    assert all(v["n"] == 1 for v in split.values())
