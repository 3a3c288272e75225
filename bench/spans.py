"""The program's own spans and request times, read for the benchmark.

The serving engine and scheduler record spans on ``time.perf_counter``
(``repro.serve.spans``) and stamp each request's ``submit_t``,
``admit_t``, ``first_t`` and ``finish_t`` on the same clock, the gate's.
This module reads them from what a run saw -- ``r.gate``, ``r.tracer``,
``r.trace`` of :class:`bench.readings.Readings` -- and the recorder's
ring. A program without the recorder gives no reading (``None``).

On the device trace's clock: the tracer takes ``t_start`` right after it
opens the ``bench.window`` span and ``t_stop`` right before it closes it,
so ``window[0] - t_start`` maps recorder times onto the trace, and the
same difference at the other end, less the first, is the drift. Each idle
gap of the traced window is then attributed to the innermost engine,
scheduler or gate span over it.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \\
        [--record <file.json.gz>]

runs one traced run of the cell as ``bench/run.py --trace 1`` does,
prints its result line, then one JSON line: the recorder's cost per span,
the split of time to first token, the clock offset and drift, and the
window's idle time by span.
``--record`` also writes the reduced trace, the spans over the traced
window and the tracer's two times (``bench/testdata/``).
"""

from __future__ import annotations

import collections
import gzip
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":      # run as a script: the checkout's packages
    _ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import trace as trace_mod  # noqa: E402
from bench.readings import percentile  # noqa: E402

#: what an idle gap covered by no named span is labelled
UNOWNED = "none"
#: a recorded span: what the ring holds, as the readers use it
Rec = collections.namedtuple("Rec", "name start end id parent args")


def recorder():
    """The program's span recorder, or ``None`` where it has none."""
    try:
        from repro.serve import spans
    except ImportError:
        return None
    return spans


# ----------------------------------------------------------- the window
def _in_window(r, t: float) -> bool:
    return r.gate.t_open <= t < r.gate.t_close


def boundary_s(recorded: Sequence, lo: float, hi: float) -> List[float]:
    """For each boundary whose ``sched.plan`` started in ``[lo, hi)``:
    end of the plan to end of its last ``engine.drain``, the dispatch and
    the device work the drain waits for."""
    plans, drains = {}, {}
    for s in recorded:
        if s.parent < 0:
            continue
        if s.name == "sched.plan":
            plans[s.parent] = s
        elif s.name == "engine.drain":
            drains[s.parent] = max(drains.get(s.parent, s.end), s.end)
    return [drains[b] - p.end for b, p in plans.items()
            if b in drains and lo <= p.start < hi]


def boundary_ms_p95(r) -> Optional[float]:
    rec = recorder()
    if rec is None:
        return None
    xs = boundary_s(rec.recent(), r.gate.t_open, r.gate.t_close)
    return 1e3 * percentile(xs, 95) if xs else None


def _request_ms_p90(r, since: str, until: str) -> Optional[float]:
    """p90 of ``until - since`` over the requests due in the window that
    carry both stamps."""
    xs = []
    for rid, rec in r.gate.records.items():
        req = r.gate.requests[rid]
        t0, t1 = getattr(req, since, -1.0), getattr(req, until, -1.0)
        if _in_window(r, rec.due) and t0 >= 0 and t1 >= 0:
            xs.append(t1 - t0)
    return 1e3 * percentile(xs, 90) if xs else None


def admit_to_first_ms_p90(r) -> Optional[float]:
    return _request_ms_p90(r, "admit_t", "first_t")


def sched_queue_ms_p90(r) -> Optional[float]:
    return _request_ms_p90(r, "submit_t", "admit_t")


def ttft_split(r) -> Dict[str, Dict[str, float]]:
    """p50 and p90 in ms, over the requests due in the window that got a
    first token: due to submission (the gate), submission to admission
    (the scheduler), admission to first token (the engine), and due to
    first token (TTFT)."""
    parts = {"gate": [], "scheduler": [], "engine": [], "ttft": []}
    for rid, rec in r.gate.records.items():
        req = r.gate.requests[rid]
        if not _in_window(r, rec.due) or getattr(req, "first_t", -1.0) < 0:
            continue
        parts["gate"].append(req.submit_t - rec.due)
        parts["scheduler"].append(req.admit_t - req.submit_t)
        parts["engine"].append(req.first_t - req.admit_t)
        parts["ttft"].append(req.first_t - rec.due)
    return {k: {"n": len(xs), "p50": 1e3 * percentile(xs, 50),
                "p90": 1e3 * percentile(xs, 90)} for k, xs in parts.items()}


# ------------------------------------------------ on the trace's clock
def clock(tr: "trace_mod.Trace", t_start: float, t_stop: float
          ) -> Tuple[float, float]:
    """(offset, drift) in seconds: recorder time plus offset is trace
    time at the window's open; drift is how far that is off at its close."""
    offset = tr.window[0] - t_start
    return offset, (tr.window[1] - t_stop) - offset


def innermost(intervals: Sequence[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """The time the ``(start, end, name)`` intervals cover, cut where any
    starts or ends, each piece named by the innermost interval over it:
    the one that started last (of two that started together, the
    shorter)."""
    edges = sorted({t for s, e, _ in intervals for t in (s, e)})
    todo = sorted(intervals, key=lambda x: (x[0], -x[1]))
    live: List[Tuple[float, float, str]] = []
    out, j = [], 0
    for a, b in zip(edges, edges[1:]):
        while j < len(todo) and todo[j][0] <= a:
            live.append(todo[j])
            j += 1
        live = [x for x in live if x[1] > a]
        if live:
            out.append((a, b, live[-1][2]))
    return out


def idle_by_span(tr: "trace_mod.Trace", recorded: Sequence, offset: float
                 ) -> Dict[str, float]:
    """Seconds of the window with no op on the first chip, by the
    innermost span over them: the recorder's spans moved onto the trace's
    clock, and the gate's spans in the trace. Idle time under no span is
    :data:`UNOWNED`."""
    lo, hi = tr.window
    cover = [(s.start + offset, s.end + offset, s.name) for s in recorded
             if s.end + offset > lo and s.start + offset < hi]
    cover += [(h.start, h.end, h.name) for h in tr.host
              if h.name != trace_mod.WINDOW_SPAN]
    busy = trace_mod.union(((o.start, o.end) for o in tr.ops[0]), lo, hi) \
        if tr.ops else []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    out: Dict[str, float] = {}
    pieces, k = innermost(cover), 0
    for s, e in gaps:
        owned = 0.0
        while k < len(pieces) and pieces[k][1] <= s:
            k += 1
        i = k
        while i < len(pieces) and pieces[i][0] < e:
            a, b, name = pieces[i]
            d = min(b, e) - max(a, s)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
                owned += d
            i += 1
        out[UNOWNED] = out.get(UNOWNED, 0.0) + (e - s) - owned
    return out


def attribution(tr: "trace_mod.Trace", recorded: Sequence, t_start: float,
                t_stop: float) -> Dict:
    """Offset, drift, the window's idle seconds by span, and the share
    of the idle time that a named span owns."""
    offset, drift = clock(tr, t_start, t_stop)
    idle = idle_by_span(tr, recorded, offset)
    total = sum(idle.values())
    return {"offset_s": offset, "drift_s": drift, "idle_s": total,
            "named_share": (1.0 - idle.get(UNOWNED, 0.0) / total
                            if total > 0 else None),
            "idle_by_span": dict(sorted(idle.items(),
                                        key=lambda kv: -kv[1]))}


# --------------------------------------------------------- a recording
def save(path, tr: "trace_mod.Trace", recorded: Sequence, t_start: float,
         t_stop: float) -> None:
    """Write the reduced trace (ops by their stable label, details left
    out), the spans that overlap the traced window and the tracer's two
    times, gzipped JSON."""
    short = trace_mod.Trace(
        ops=[[trace_mod.Op(trace_mod.op_label(o), o.start, o.end)
              for o in dev] for dev in tr.ops],
        modules=[[trace_mod.Op(trace_mod.base_name(m.name), m.start, m.end)
                  for m in dev] for dev in tr.modules],
        host=tr.host, window=tr.window)
    keep = [[s.name, s.start, s.end, s.id, s.parent, s.args]
            for s in recorded if s.end >= t_start and s.start <= t_stop]
    data = {"trace": short.to_json(), "spans": keep,
            "tracer": [t_start, t_stop]}
    Path(path).write_bytes(gzip.compress(json.dumps(data).encode()))


def load(path) -> Tuple["trace_mod.Trace", List[Rec], float, float]:
    """What :func:`save` wrote: trace, spans, ``t_start``, ``t_stop``."""
    data = json.loads(gzip.decompress(Path(path).read_bytes()))
    return (trace_mod.Trace.from_json(data["trace"]),
            [Rec(*row) for row in data["spans"]], *data["tracer"])


# ------------------------------------------------------------ the command
def cost_us(n: int = 100_000) -> Dict[str, float]:
    """Microseconds per span on this host, recorder on and off, with no
    profiler running (the benchmark's untraced runs)."""
    spans = recorder()
    out = {}
    for on in (True, False):
        spans.enabled = on
        t0 = time.perf_counter()
        for i in range(n):
            with spans.span("cost", rid=i):
                pass
        out["on" if on else "off"] = 1e6 * (time.perf_counter() - t0) / n
    spans.enabled = True
    spans.clear()
    return out


def main(argv=None) -> int:
    import argparse
    import bench.run as run_mod
    from bench import harness, traffic

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", type=Path, default=None)
    args = ap.parse_args(argv)
    cost = cost_us()
    import jax
    jax.config.update("jax_compilation_cache_dir", str(run_mod.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    root = run_mod.ROOT
    spec = harness.load_benchmark(root)
    cell, entry = harness.find_cell(spec, args.workload)
    config = json.loads((root / entry["file"]).read_text())
    mix = traffic.load_mix(cell["traffic"], root / "bench" / "traffic")
    device = run_mod.device_check(cell["chips"])
    seen = {}

    def keeping(read):
        def wrapped(r):
            seen["r"] = r
            return read(r)
        return wrapped

    readers = run_mod.load_readers(
        [m["name"] for m in harness.metrics_for(spec, cell["name"], True)],
        root / "bench" / "metrics")
    result = harness.run(
        spec=spec, cell=cell, config=config, mix=mix, seed=args.seed,
        seconds=args.seconds, trace=True, device=device,
        t_start=run_mod.T_START, compiles=harness.CompileLog(),
        readers={k: keeping(v) for k, v in readers.items()},
        log=lambda s: print(s, flush=True))
    print(result.line(), flush=True)
    r = seen["r"]
    recorded = recorder().recent()
    out = {"cost_us_per_span": cost, "ttft_split_ms": ttft_split(r),
           **attribution(r.trace, recorded, r.tracer.t_start,
                         r.tracer.t_stop)}
    print(json.dumps(out), flush=True)
    if args.record is not None:
        save(args.record, r.trace, recorded, r.tracer.t_start,
             r.tracer.t_stop)
    return 0


if __name__ == "__main__":
    sys.exit(main())
