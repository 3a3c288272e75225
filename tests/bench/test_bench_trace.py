"""Trace reduction: busy and idle time as a union of op intervals,
kernel and program time by stable name, collective exposure, and idle
gaps labelled by the gate's host spans -- on hand-built traces and on
small traces recorded on one TPU v5e and on a four-chip v5e host
(``bench/testdata/``)."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace as tr
from bench.trace import Op, Trace

TESTDATA = Path(__file__).resolve().parents[2] / "bench" / "testdata"


def hand_built() -> Trace:
    dev0 = [Op("fusion.1", 0.0, 1.0, "7"),
            Op("fusion.2", 0.5, 1.5, "7"),              # overlaps fusion.1
            Op("paged_flash_decode.3", 2.0, 3.0, "7",
               "jit(run)/while/body/jit(paged_flash_decode)/pallas_call"),
            Op("all-reduce.4", 2.5, 4.0, "7"),          # 1 s exposed
            Op("custom-call.5", 6.0, 7.0, "9",
               "jit(admit)/jit(flash_attention)/pallas_call")]
    dev1 = [Op("fusion.1", 0.0, 2.0, "7")]
    mods = [[Op("jit_run", 0.0, 4.0, "7"), Op("jit_run", 6.0, 7.0, "9")],
            [Op("jit_run", 0.0, 2.0, "7")]]
    host = [Op(tr.WINDOW_SPAN, 0.0, 10.0), Op("gate.plan", 4.2, 4.8),
            Op("gate.wait", 7.0, 9.5)]
    return Trace(ops=[dev0, dev1], modules=mods, host=host,
                 window=(0.0, 10.0))


def test_union_and_subtract():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.union([(0, 2), (5, 9)], 1, 6) == [(1, 2), (5, 6)]
    assert tr.subtract([(0, 4), (6, 8)], [(1, 2), (3, 7)]) == \
        pytest.approx(1 + 1 + 1)


def test_busy_kernel_program_and_collective_time():
    t = hand_built()
    assert tr.window_s(t) == 10.0
    # chip 0 busy 0-1.5, 2-4, 6-7 = 4.5 s; chip 1 busy 2 s
    assert tr.busy_s(t) == pytest.approx((4.5 + 2.0) / 2)
    assert tr.kernel_s(t, "page_walk") == pytest.approx(1.0 / 2)
    assert tr.kernel_s(t, "flash") == pytest.approx(1.0 / 2)
    # the decode program is the one holding the page walk: module 7 on
    # chip 0; chip 1 ran no page walk
    assert tr.program_s(t, "decode") == pytest.approx((4.0 + 0.0) / 2)
    assert tr.program_s(t, "admit") == pytest.approx((1.0 + 0.0) / 2)
    assert tr.collective_exposed_s(t) == pytest.approx(1.0 / 2)


def test_no_collective_reads_nothing():
    t = hand_built()
    t.ops = [[o for o in d if "all-reduce" not in o.name] for d in t.ops]
    assert tr.collective_exposed_s(t) is None


def test_async_collective_pair_is_found_by_its_own_name():
    """An async all-reduce shows as a ``-start`` and a ``-done`` op; a
    v5e trace names each op by its whole instruction, so a fusion that
    reads the collective's result names it too, and a loop holds them."""
    dev = [Op("%while.1 = (s32[]) while(%tuple.2), body=%body", 0.0, 10.0),
           Op("%all-reduce-start.3 = bf16[48,5120] all-reduce-start("
              "%fusion.1)", 1.0, 1.5),
           Op("%fusion.4 = bf16[48,5120] fusion(%x)", 1.5, 3.0),
           Op("%all-reduce-done.3 = bf16[48,5120] all-reduce-done("
              "%all-reduce-start.3)", 3.0, 4.0),
           Op("%fusion.5 = bf16[48,5120] fusion(%all-reduce-done.3)",
              4.0, 6.0)]
    t = Trace(ops=[dev], modules=[[]], host=[], window=(0.0, 10.0))
    assert [tr.is_collective(o) for o in dev] == [False, True, False, True,
                                                  False]
    # start 1-1.5 and done 3-4 run alone; the loop around them is no op
    assert tr.collective_exposed_s(t) == pytest.approx(1.5)


def test_breakdown_labels_gaps_by_host_span():
    b = tr.breakdown(hand_built())
    gaps = dict((k, v) for k, v in b["idle_gaps"])
    # 7-10 is mostly gate.wait, 4-6 mostly gate.plan, 1.5-2 the engine
    assert b["idle_gaps"][0] == ["gate.wait", pytest.approx(3.0)]
    assert gaps["gate.plan"] == pytest.approx(2.0)
    assert gaps["engine"] == pytest.approx(0.5)
    names = [k for k, _ in b["device_ops"]]
    assert names[0] == "fusion" and "paged_flash_decode" in names


def test_json_round_trip(tmp_path):
    t = hand_built()
    tr.save_json(t, tmp_path / "t.json")
    assert tr.load_json(tmp_path / "t.json") == t


def test_recorded_v5e_trace():
    """Two decode chunks and the admissions before them, cut from a traced
    run of the chat cell on one TPU v5e (seed 101). There each op is
    named by its whole HLO instruction and no op carries a program id."""
    t = tr.load_json(TESTDATA / "v5e_chat_decode.json.gz")
    assert t.ops[0][0].name.startswith("%")
    assert 0.9 * tr.window_s(t) < tr.busy_s(t) <= tr.window_s(t)
    walk, flash = tr.kernel_s(t, "page_walk"), tr.kernel_s(t, "flash")
    # 2 chunks x 8 steps x 36 layers of page walk, and some prefill
    assert sum(tr.is_kernel(o, "page_walk") for o in t.ops[0]) == 576
    assert 0 < flash < walk < tr.program_s(t, "decode") <= tr.busy_s(t)
    assert flash < tr.program_s(t, "admit") < walk
    assert tr.collective_exposed_s(t) is None        # one chip
    b = tr.breakdown(t)
    assert b["device_ops"][0] == ["paged_flash_decode", pytest.approx(walk)]
    assert "while" not in dict(b["device_ops"])
    assert {k for k, _ in b["idle_gaps"]} <= {"engine", "gate.plan",
                                              "gate.wait"}


def test_recorded_four_chip_v5e_trace():
    """32 ms of a traced run of mistral-nemo-12b-tp4.chat-batch on a
    four-chip v5e host (seed 7): a decode chunk's end, a gate.plan gap,
    and an admission. There the collectives are synchronous ops named
    ``%all-reduce.N`` and ``%all-gather.N``, with no async pair; fusions
    that read them name them among their operands, and the loops that
    hold them are ops of their own."""
    t = tr.load_json(TESTDATA / "v5e_tp4_admit_decode.json.gz")
    assert len(t.ops) == 4
    for dev in t.ops:
        coll = [o for o in dev if tr.is_collective(o)]
        assert {tr.base_name(o.name) for o in coll} == {"all-reduce",
                                                        "all-gather"}
        assert all(o.name.startswith(("%all-reduce.", "%all-gather."))
                   for o in coll)
        # operands name collectives too; those ops are not collectives
        assert any(tr.COLLECTIVE.search(o.name) and not tr.is_collective(o)
                   for o in dev)
    assert any(tr.base_name(o.name) == "while" for o in t.ops[0])
    # a synchronous collective runs alone on its chip: all of it is
    # exposed, the loops around it notwithstanding
    coll_s = sum(sum(o.end - o.start for o in dev if tr.is_collective(o))
                 for dev in t.ops) / 4
    assert 0 < tr.collective_exposed_s(t) == pytest.approx(coll_s, rel=1e-3)
    # busy and idle per chip, averaged: not the union over the chips
    per_chip = [tr.length(tr.union(((o.start, o.end) for o in dev),
                                   *t.window)) for dev in t.ops]
    every = tr.length(tr.union(((o.start, o.end) for dev in t.ops
                                for o in dev), *t.window))
    assert len(set(per_chip)) == 4
    assert tr.busy_s(t) == pytest.approx(sum(per_chip) / 4, rel=1e-12)
    assert tr.busy_s(t) < every < tr.window_s(t)
    # the gap is the scheduler's plan, on every chip
    assert tr.breakdown(t)["idle_gaps"][0][0] == "gate.plan"
    assert 0 < tr.kernel_s(t, "page_walk") and 0 < tr.kernel_s(t, "flash")
