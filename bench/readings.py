"""From what one run saw -- the gate's stamps and boundaries, the
scheduler's counters and the device trace -- to numbers. The per-layer
readers under ``bench/metrics/`` each pick one of these; a reading with
nothing to read returns ``None``.

Work is counted, and kernels and programs named, by the configuration's
model family where it gives them (``decode_token_flops``,
``prefill_flops``, ``KERNEL_WORK``, ``KERNELS``, ``PROGRAMS``: see
``bench/families/dense.py``), and by ``bench/counts.py`` (dense GQA with
SwiGLU, :data:`WORK`) and ``bench/trace.py``'s tables otherwise.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import counts, peaks
from bench import trace as trace_mod


def _total(work) -> Tuple[float, float]:
    flops = nbytes = 0.0
    for f, n in work:
        flops, nbytes = flops + f, nbytes + n
    return flops, nbytes


#: kernel -> ``fn(shape, boundary)``, the (flops, bytes) one traced
#: boundary needed in that kernel of ``bench/trace.py``'s own table
WORK = {
    "page_walk": lambda shape, b: _total(
        counts.page_walk(shape, a) for a in counts.decode_steps(b.decode)),
    "flash": lambda shape, b: _total(
        counts.flash(shape, length) for length in b.admitted),
}


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile; 0 for an empty sample."""
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) \
        else 0.0


class Readings:
    def __init__(self, *, gate, seconds: float, shape: Dict, n_slots: int,
                 sync_interval: int, chips: int, kind: str,
                 trace: Optional["trace_mod.Trace"] = None, tracer=None,
                 setup_s: float = 0.0, family=None):
        self.gate, self.seconds, self.shape = gate, seconds, shape
        self.n_slots, self.sync = n_slots, sync_interval
        self.chips, self.trace, self.tracer = chips, trace, tracer
        self.peak = peaks.peak_for(kind)
        self.setup_s = setup_s
        self.kernels, self.programs = trace_mod.tables(family)
        self.work = {**WORK, **getattr(family, "KERNEL_WORK", {})}
        self.decode_token_flops = getattr(family, "decode_token_flops",
                                          counts.decode_token_flops)
        self.prefill_flops = getattr(family, "prefill_flops",
                                     counts.prefill_flops)

    # ---------------------------------------------------------- the window
    def _in(self, t: float) -> bool:
        return self.gate.t_open <= t < self.gate.t_close

    def due_in_window(self):
        return [r for r in self.gate.records.values() if self._in(r.due)]

    def n_in_window(self) -> int:
        return len(self.due_in_window())

    def window_boundaries(self):
        return [b for b in self.gate.boundaries
                if self._in(b.t_plan) and 0 <= b.t_drain <= self.gate.t_close]

    def traced_boundaries(self):
        if self.tracer is None or self.tracer.t_stop is None:
            return []
        return self.gate.boundaries[self.tracer.first:self.tracer.last]

    # ----------------------------------------------------------- end to end
    def ttfts_s(self) -> List[float]:
        """Due time to first token, for every request due in the window; a
        request that got none by the drain cap counts at the cap."""
        cap = self.gate.t_close + self.gate.drain_cap_s
        return [(r.first_t if r.deliveries else cap) - r.due
                for r in self.due_in_window()]

    def itls_s(self) -> List[float]:
        """For every token delivered in the window after its request's
        first drain: (this drain - the request's previous drain) / tokens
        got at this drain."""
        out = []
        for r in self.gate.records.values():
            for (t0, _), (t1, k) in zip(r.deliveries, r.deliveries[1:]):
                if self._in(t1):
                    out.extend([(t1 - t0) / k] * k)
        return out

    def output_tokens(self) -> int:
        return sum(k for r in self.gate.records.values()
                   for t, k in r.deliveries if self._in(t))

    def end_to_end(self, name: str) -> Optional[float]:
        if name == "ttft_p90_ms":
            xs = self.ttfts_s()
            return 1e3 * percentile(xs, 90) if xs else None
        if name == "itl_p95_ms":
            xs = self.itls_s()
            return 1e3 * percentile(xs, 95) if xs else None
        if name == "output_tok_s":
            return self.output_tokens() / self.seconds
        if name == "setup_s":
            return self.setup_s
        raise KeyError(f"no end-to-end reading {name!r}")

    def attempted_failed(self) -> Tuple[int, int]:
        """Open loop: requests due in the window, and those of them with no
        first token by the drain cap. Backlog: requests admitted in the
        window, and those of them rejected."""
        if self.gate.backlog:
            mine = [r for r in self.gate.records.values()
                    if self._in(r.admit_t)]
            return len(mine), sum(r.rejected for r in mine)
        mine = self.due_in_window()
        return len(mine), sum(not r.deliveries for r in mine)

    # ---------------------------------------------------- host and counters
    def queue_wait_p90_ms(self) -> Optional[float]:
        xs = [r.admit_t - r.due for r in self.due_in_window()
              if r.admit_t >= 0]
        return 1e3 * percentile(xs, 90) if xs else None

    def plan_ms_p95(self) -> Optional[float]:
        xs = [b.plan_s for b in self.window_boundaries()]
        return 1e3 * percentile(xs, 95) if xs else None

    def decode_occupancy_pct(self) -> Optional[float]:
        bs = self.window_boundaries()
        if not bs:
            return None
        tokens = sum(g for b in bs for _, g in b.decode)
        return 100.0 * tokens / (len(bs) * self.sync * self.n_slots)

    def pages_peak_pct(self) -> Optional[float]:
        """Most layer-0 pages in use at once over the run, of the pool's
        pages (the null page left out), in %."""
        pool = self.gate.page_pool
        if pool is None or pool.n_pages <= 1:
            return None
        return 100.0 * pool.high_water / (pool.n_pages - 1)

    # ---------------------------------------------------------------- trace
    def busy_s(self) -> Optional[float]:
        return trace_mod.busy_s(self.trace) if self.trace else None

    def window_s(self) -> Optional[float]:
        return trace_mod.window_s(self.trace) if self.trace else None

    def idle_pct(self) -> Optional[float]:
        if not self.trace:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s())

    def collective_exposed_pct(self) -> Optional[float]:
        """Share of the traced window in which a collective ran with no
        other op on its chip, averaged over chips, in %."""
        if not self.trace:
            return None
        exposed = trace_mod.collective_exposed_s(self.trace)
        return None if exposed is None else \
            100.0 * exposed / self.window_s()

    def breakdown(self) -> Optional[Dict]:
        return (trace_mod.breakdown(self.trace, kernels=self.kernels)
                if self.trace else None)

    def _work(self, kernel: str) -> Tuple[float, float]:
        """(flops, bytes) the traced boundaries needed in ``kernel``."""
        work = self.work.get(kernel)
        return _total(work(self.shape, b) for b in self.traced_boundaries()) \
            if work else (0.0, 0.0)

    def roofline_pct(self, kernel: str) -> Optional[float]:
        """Least time the needed work could take on the chips over the
        kernel's device time (averaged over chips), in %."""
        if not self.trace:
            return None
        seconds = trace_mod.kernel_s(self.trace, kernel, self.kernels)
        flops, nbytes = self._work(kernel)
        if seconds <= 0 or flops <= 0:
            return None
        share, _ = counts.roofline_share(
            flops, nbytes, seconds, self.chips * self.peak.flops,
            self.chips * self.peak.hbm_bytes)
        return share

    def step_mfu_pct(self, program: str) -> Optional[float]:
        """Model flops of the traced boundaries' decode tokens (or true
        prompt tokens) over the decode (or admission) programs' device
        time, chips and peak, in %."""
        if not self.trace:
            return None
        seconds = trace_mod.program_s(self.trace, program, self.kernels,
                                      self.programs)
        bs = self.traced_boundaries()
        if program == "decode":
            flops = sum(self.decode_token_flops(self.shape, a)
                        for b in bs for a in counts.decode_steps(b.decode))
        else:
            flops = sum(self.prefill_flops(self.shape, length)
                        for b in bs for length in b.admitted)
        if seconds <= 0 or flops <= 0:
            return None
        return 100.0 * flops / (seconds * self.chips * self.peak.flops)
