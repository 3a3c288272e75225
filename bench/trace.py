"""From a profiler trace (``.xplane.pb``) to device busy time, kernel and
program time by stable name, collective exposure and the breakdown.

:func:`load` keeps only what the reductions read, as plain lists, so a
trace can also be stored as JSON (``bench/testdata/``). Times are seconds
on the trace's own clock. The window is the host span
:data:`WINDOW_SPAN`, which the harness opens right after the profiler
starts and closes right before it stops.

Kernels and programs are found by name through :data:`KERNELS` and
:data:`PROGRAMS`; a model family may add entries of its own (its
``KERNELS`` and ``PROGRAMS``), joined to these by :func:`tables`, and the
reductions that find kernels take the joined tables.
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
#: host spans the gate writes; an idle gap is labelled by the one it
#: overlaps most, else by the engine's own loop
HOST_SPANS = ("gate.plan", "gate.wait")
#: the program's Pallas kernels, by the jitted function that calls each:
#: XLA names the kernel's custom call after it (``paged_flash_decode.3``),
#: and its framework op ends in ``jit(paged_flash_decode)/pallas_call``
KERNELS = {
    "page_walk": "paged_flash_decode",
    "flash": "flash_attention",
}
#: which kernel marks which program: a decode chunk walks pages, an
#: admission runs flash prefill
PROGRAMS = {"decode": "page_walk", "admit": "flash"}
#: ops that only hold other ops (a loop, a branch, a call): left out of
#: the breakdown, which would count their time twice
CONTAINERS = frozenset({"while", "conditional", "call"})
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")
_STAT_KEYS = ("long_name", "tf_op", "hlo_op", "kernel_details")


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float
    program: str = ""
    detail: str = ""        # long name, framework op, ... where the trace has them


@dataclasses.dataclass
class Trace:
    ops: List[List[Op]]             # per device, the XLA ops
    modules: List[List[Op]]         # per device, the XLA module runs
    host: List[Op]                  # host spans (TraceAnnotation names)
    window: Tuple[float, float]

    def to_json(self) -> Dict:
        enc = lambda ops: [[o.name, o.start, o.end, o.program, o.detail]
                           for o in ops]
        return {"ops": [enc(d) for d in self.ops],
                "modules": [enc(d) for d in self.modules],
                "host": enc(self.host), "window": list(self.window)}

    @classmethod
    def from_json(cls, d: Dict) -> "Trace":
        dec = lambda rows: [Op(*r) for r in rows]
        return cls(ops=[dec(x) for x in d["ops"]],
                   modules=[dec(x) for x in d["modules"]],
                   host=dec(d["host"]), window=tuple(d["window"]))


def _is_device(name: str) -> bool:
    return name.startswith("/device:TPU:") and "SparseCore" not in name


def load(path) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if _is_device(plane.name):
            dev_ops, dev_mods = [], []
            for line in plane.lines:
                target = {"XLA Ops": dev_ops,
                          "XLA Modules": dev_mods}.get(line.name)
                if target is None:
                    continue
                for e in line.events:
                    stats = dict(e.stats)
                    detail = " | ".join(str(stats[k]) for k in _STAT_KEYS
                                        if k in stats)
                    prog = str(stats.get("program_id",
                                         stats.get("hlo_module", "")))
                    target.append(Op(e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9,
                                     prog, detail))
            if dev_ops:
                ops.append(dev_ops)
                modules.append(dev_mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name in HOST_SPANS:
                        host.append(Op(e.name, e.start_ns * 1e-9,
                                       (e.start_ns + e.duration_ns) * 1e-9))
    spans = [h for h in host if h.name == WINDOW_SPAN]
    if spans:
        window = (spans[0].start, spans[0].end)
    else:   # no window span: the span of the device work
        every = [o for d in ops for o in d]
        window = (min((o.start for o in every), default=0.0),
                  max((o.end for o in every), default=0.0))
    return Trace(ops=ops, modules=modules, host=host, window=window)


def save_json(tr: Trace, path) -> None:
    """Write ``tr`` as JSON, gzipped where ``path`` ends in ``.gz``."""
    data = json.dumps(tr.to_json()).encode()
    Path(path).write_bytes(gzip.compress(data) if str(path).endswith(".gz")
                           else data)


def load_json(path) -> Trace:
    data = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    return Trace.from_json(json.loads(data))


# ------------------------------------------------------------ intervals
def union(intervals, lo: float = float("-inf"), hi: float = float("inf")
          ) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> float:
    """Length of the merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _mean(xs) -> float:
    xs = list(xs)
    return sum(xs) / len(xs) if xs else 0.0


# ---------------------------------------------------------- reductions
def window_s(tr: Trace) -> float:
    return tr.window[1] - tr.window[0]


def busy_s(tr: Trace) -> float:
    """Seconds in the window in which some op ran, averaged over chips."""
    return _mean(length(union(((o.start, o.end) for o in dev), *tr.window))
                 for dev in tr.ops)


def base_name(name: str) -> str:
    """An op name without its instance number (``fusion.12`` -> ``fusion``).
    A v5e trace names an op by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``): the name is its first word."""
    name = name.lstrip("%").split(" ", 1)[0]
    return re.sub(r"[.\d]+$", "", name)


def tables(family=None) -> Tuple[Dict[str, str], Dict[str, str]]:
    """The kernel and program tables: :data:`KERNELS` and :data:`PROGRAMS`
    with a model family's own ``KERNELS`` (name -> the jitted function
    that calls the kernel, or its name) and ``PROGRAMS`` (program -> the
    kernel that marks it) laid over them."""
    kernels = {**KERNELS, **{k: getattr(fn, "__name__", fn) for k, fn in
                             getattr(family, "KERNELS", {}).items()}}
    return kernels, {**PROGRAMS, **getattr(family, "PROGRAMS", {})}


def is_kernel(op: Op, kernel: str, kernels: Dict[str, str] = KERNELS
              ) -> bool:
    name = kernels[kernel]
    return (base_name(op.name) == name
            or f"jit({name})/pallas_call" in op.detail)


def kernel_s(tr: Trace, kernel: str, kernels: Dict[str, str] = KERNELS
             ) -> float:
    """Device seconds of ``kernel``'s calls, averaged over chips."""
    return _mean(sum(o.end - o.start for o in dev
                     if is_kernel(o, kernel, kernels))
                 for dev in tr.ops)


def program_s(tr: Trace, program: str, kernels: Dict[str, str] = KERNELS,
              programs: Dict[str, str] = PROGRAMS) -> float:
    """Device seconds of the module runs that hold a call of
    ``program``'s marking kernel, averaged over chips. Runs are matched
    by time: a v5e trace gives its ops no program id."""
    kernel = programs[program]
    per_dev = []
    for dev_ops, dev_mods in zip(tr.ops, tr.modules):
        marks = sorted(o.start for o in dev_ops
                       if is_kernel(o, kernel, kernels))
        per_dev.append(sum(
            m.end - m.start for m in dev_mods
            if bisect.bisect_left(marks, m.start) < len(marks)
            and marks[bisect.bisect_left(marks, m.start)] < m.end))
    return _mean(per_dev)


def is_collective(op: Op) -> bool:
    """A collective by the op's own name (an async pair's ``-start`` and
    ``-done`` too), not by the operands a v5e trace lists after it."""
    return bool(COLLECTIVE.search(base_name(op.name)))


def collective_exposed_s(tr: Trace) -> Optional[float]:
    """Seconds in which a collective ran with no other op on that chip,
    averaged over chips; ``None`` where the trace holds no collective. A
    loop or other container that holds the collective is no other op."""
    found, per_dev = False, []
    for dev in tr.ops:
        coll = union(((o.start, o.end) for o in dev if is_collective(o)),
                     *tr.window)
        comp = union(((o.start, o.end) for o in dev
                      if not is_collective(o)
                      and base_name(o.name) not in CONTAINERS), *tr.window)
        found = found or bool(coll)
        per_dev.append(subtract(coll, comp))
    return _mean(per_dev) if found else None


def op_label(op: Op, kernels: Dict[str, str] = KERNELS) -> str:
    """A name that survives renumbering: the kernel's name for the
    Pallas kernels, else the op name without its instance number."""
    for kernel, name in kernels.items():
        if is_kernel(op, kernel, kernels):
            return name
    return base_name(op.name)


def idle_gaps(tr: Trace) -> List[Tuple[str, float]]:
    """Gaps in the window with no op on the first chip, each labelled by
    the host span that overlaps it most (``engine`` where none does)."""
    lo, hi = tr.window
    busy = union(((o.start, o.end) for o in tr.ops[0]), lo, hi) \
        if tr.ops else []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    out = []
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        best, label = 0.0, "engine"
        for h in tr.host:
            if h.name == WINDOW_SPAN:
                continue
            ov = min(e, h.end) - max(s, h.start)
            if ov > best:
                best, label = ov, h.name
        out.append((label, e - s))
    return out


def breakdown(tr: Trace, top: int = 10, kernels: Dict[str, str] = KERNELS
              ) -> Dict[str, List]:
    """The ops that took most device time (averaged over chips; loops and
    other containers left out) and the longest idle gaps, each at most
    ``top`` entries."""
    tot: Dict[str, float] = {}
    for dev in tr.ops:
        for o in dev:
            if (o.end > tr.window[0] and o.start < tr.window[1]
                    and base_name(o.name) not in CONTAINERS):
                key = op_label(o, kernels)
                tot[key] = tot.get(key, 0.0) + (o.end - o.start) / len(tr.ops)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(tr), key=lambda g: -g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
