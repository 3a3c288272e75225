"""The arrival gate: a ``Scheduler`` that admits requests on a schedule
and stamps every token with the host time at which the client got it.

``Engine.serve`` takes its stream up front and loops ``while
sch.has_work()``, calling ``sch.plan_boundary`` at the start of every
drain boundary. The gate hooks both:

* ``has_work()`` runs once per drain, right after the engine appended the
  drained tokens to each request. It stamps them with the current time,
  and says whether the run goes on.
* ``plan_boundary()`` first submits every request whose due time has
  passed (open loop) or tops the queue up to ``n_slots`` (backlog), then
  times the parent's plan. With nothing live or queued it sleeps until
  the next due time, so the device idles as a real server's would.

Times are host ``perf_counter`` seconds. The gate's only use of JAX is
the profiler's host spans ``gate.wait`` and ``gate.plan``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _Span  # host spans in the trace

from repro.serve.scheduler import DRAINED, REJECTED, Request, Scheduler


@dataclasses.dataclass
class Record:
    """What the client saw of one request."""
    due: float
    prompt_len: int
    max_new_tokens: int
    admit_t: float = -1.0           # plan time of the admitting boundary
    deliveries: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list)       # (drain time, tokens got there)
    rejected: bool = False

    @property
    def first_t(self) -> float:
        return self.deliveries[0][0] if self.deliveries else -1.0

    @property
    def n_tokens(self) -> int:
        return sum(k for _, k in self.deliveries)


@dataclasses.dataclass
class Boundary:
    """One drain boundary: when it was planned, how long the plan took,
    the prompts it admitted, and the decode chunk's slots as
    ``(frontier at chunk start, tokens produced)``."""
    t_plan: float
    plan_s: float
    admitted: List[int]
    decode: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    t_drain: float = -1.0


class Gate(Scheduler):
    """Open-loop or backlog admission over the window
    ``[t0 + warm_in, t0 + warm_in + seconds)``, ``t0`` being the first
    ``has_work()`` call (the start of serving)."""

    def __init__(self, *, n_slots: int, pages, arrivals: Iterator,
                 warm_in_s: float, seconds: float, drain_cap_s: float = 0.0,
                 backlog: bool = False,
                 on_drain: Optional[Callable[["Gate", float], None]] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep):
        super().__init__(n_slots=n_slots, pages=pages)
        self._arrivals = arrivals
        self._next = None
        self.warm_in_s, self.seconds = warm_in_s, seconds
        self.drain_cap_s = drain_cap_s
        self.backlog = backlog
        self.on_drain = on_drain
        self.clock, self.sleep = clock, sleep
        self.t0 = self.t_open = self.t_close = None
        self.records: Dict[int, Record] = {}
        self.requests: Dict[int, Request] = {}
        self.boundaries: List[Boundary] = []
        self._live: Dict[int, int] = {}         # rid -> tokens stamped
        self._chunk: List[Tuple[Request, int]] = []
        self.wait_s = 0.0

    # ----------------------------------------------------------- arrivals
    def _peek(self):
        """The next arrival, or ``None`` once a finite schedule ends."""
        if self._next is None:
            self._next = next(self._arrivals, None)
        return self._next

    def _submit(self, due: float) -> None:
        arr, self._next = self._next, None
        req = self.submit_request(Request(rid=0, prompt=arr.prompt,
                                          max_new_tokens=arr.max_new_tokens))
        self.records[req.rid] = Record(due=due, prompt_len=len(arr.prompt),
                                       max_new_tokens=arr.max_new_tokens)
        self.requests[req.rid] = req
        self._live[req.rid] = 0

    def _offer(self, now: float) -> None:
        if self.backlog:
            while len(self.queue) < self.n_slots and self._peek():
                self._submit(now)
            return
        while self._peek() and self.t0 + self._next.due_s <= now:
            self._submit(self.t0 + self._next.due_s)

    # ------------------------------------------------------------ stamping
    def _stamp(self, now: float) -> None:
        for req, l0 in self._chunk:
            seen = self._live.get(req.rid)
            if seen is None:
                continue
            got = len(req.tokens) - seen
            first = seen == 0 and got > 0
            if self.boundaries:
                self.boundaries[-1].decode.append((l0, max(got - first, 0)))
        if self.boundaries and self.boundaries[-1].t_drain < 0:
            self.boundaries[-1].t_drain = now
        self._chunk = []
        for rid in list(self._live):
            req, rec = self.requests[rid], self.records[rid]
            got = len(req.tokens) - self._live[rid]
            if got > 0:
                rec.deliveries.append((now, got))
                self._live[rid] = len(req.tokens)
            if req.status == REJECTED:
                rec.rejected = True
            if req.status in (DRAINED, REJECTED):
                del self._live[rid]

    def pending_first(self) -> int:
        """Requests due in the window that have no first token yet, also
        those due since the last boundary and not yet submitted."""
        unsubmitted = (not self.backlog and self._peek() is not None
                       and self.t0 + self._next.due_s < self.t_close)
        return unsubmitted + sum(
            1 for r in self.records.values()
            if self.t_open <= r.due < self.t_close and not r.deliveries
            and not r.rejected)

    # ----------------------------------------------------- scheduler hooks
    def has_work(self) -> bool:
        now = self.clock()
        if self.t0 is None:
            self.t0 = now
            self.t_open = now + self.warm_in_s
            self.t_close = self.t_open + self.seconds
        self._stamp(now)
        if self.on_drain is not None:
            self.on_drain(self, now)
        if now < self.t_close:
            return True
        if self.backlog or now >= self.t_close + self.drain_cap_s:
            return False
        return self.pending_first() > 0

    def plan_boundary(self, *, chunk_tokens: int, max_len: int):
        now = self.clock()
        if (not self.backlog and not self.queue and not self.active
                and self._peek()):
            with _Span("gate.wait"):
                wait = self.t0 + self._next.due_s - now
                if wait > 0:
                    self.sleep(wait)
                    self.wait_s += wait
            now = self.clock()
        self._offer(now)
        t_plan = self.clock()
        with _Span("gate.plan"):
            plan = super().plan_boundary(chunk_tokens=chunk_tokens,
                                         max_len=max_len)
        plan_s = self.clock() - t_plan
        for _, req in plan.admits:
            self.records[req.rid].admit_t = t_plan
        self.boundaries.append(Boundary(
            t_plan=t_plan, plan_s=plan_s,
            admitted=[req.prompt_len for _, req in plan.admits]))
        self._chunk = [(req, req.cache_len) for req in self.active.values()]
        return plan
