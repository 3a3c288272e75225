"""One run of one cell: build the engine from the seed, warm every shape
the cell's traffic uses, serve through the arrival gate for the window,
check the served tokens against the plain reference, and reduce what was
seen to the cell's metrics.

``run.py`` is the command; this module holds the run so that the tests
can drive it without a chip.
"""

from __future__ import annotations

import dataclasses
import json
import math
import shutil
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.models import build_model
from repro.models.config import ModelConfig
from repro.serve import scheduler as sched_mod
from repro.serve.engine import Engine, EngineConfig
from repro.serve.pool import pool_bytes_beside

from bench import readings, reference, traffic, weights
from bench import trace as trace_mod
from bench.gate import Gate

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: seconds of the window a traced run records: a few seconds of steady
#: serving, short enough that reading the trace stays well inside a run
TRACE_SECONDS = 5.0


# ------------------------------------------------------------- definitions
def load_benchmark(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(spec: Dict, name: str):
    """The cell ``name`` and its configuration entry."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def metrics_for(spec: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metric entries a run of ``cell`` reports: its end-to-end
    metrics, or with ``trace`` its per-layer ones."""
    entries = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def model_config(config: Dict) -> ModelConfig:
    """The program's config for a configuration file's ``model`` section
    (Hugging Face key names)."""
    m = config["model"]
    if m.get("hidden_act", "silu") != "silu":
        raise ValueError(f"unsupported activation {m['hidden_act']!r}")
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim") or config["assumed"]["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=float(m["rope_theta"]),
        qkv_bias=bool(config["assumed"]["qkv_bias"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        norm_eps=float(m["rms_norm_eps"]))


# --------------------------------------------------------------- compiles
class CompileLog:
    """Times of JAX's backend-compile events (a persistent-cache hit
    records one too), so compiles inside the window can be counted."""

    def __init__(self):
        self.events: List[float] = []
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append(time.perf_counter())
            self.seconds += duration

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t < hi for t in self.events)


# ------------------------------------------------------------------ trace
class Tracer:
    """Starts the profiler at the first drain in the window and stops it
    at the first drain ``seconds`` later (or at the window's close), so
    the traced device work is exactly that of whole boundaries."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.state = "idle"
        self.t_start = self.t_stop = None
        self.first = self.last = 0
        self._span = None

    def __call__(self, gate: Gate, now: float) -> None:
        if self.state == "idle" and now >= gate.t_open:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
            self._span.__enter__()
            self.t_start = time.perf_counter()
            self.first = len(gate.boundaries)
            self.state = "on"
        elif self.state == "on" and (now >= self.t_start + self.seconds
                                     or now >= gate.t_close):
            self.stop(gate)

    def stop(self, gate: Gate) -> None:
        if self.state != "on":
            return
        self.t_stop = time.perf_counter()
        self.last = len(gate.boundaries)
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def read(self, keep: Optional[Path] = None) -> Optional[trace_mod.Trace]:
        """The reduced trace; ``keep`` also writes it there as JSON."""
        try:
            files = sorted(Path(self.dir).rglob("*.xplane.pb"))
            tr = trace_mod.load(files[-1]) if files else None
            if tr is not None and keep is not None:
                trace_mod.save_json(tr, keep)
            return tr
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ------------------------------------------------------------------ build
@dataclasses.dataclass
class Served:
    engine: Engine
    geom: Any
    params: Any


def build(config: Dict, mix: Dict, seed: int,
          kv_quant: Optional[str] = None, chips: int = 1) -> Served:
    """Weights from the seed, the pool beside them, and the engine, as a
    one-chip deployment builds them. ``kv_quant`` switches on the
    program's own quantized KV pages (the control of ``bench/control.py``;
    the cells serve bf16 pages). A cell on more chips is refused: this
    builds no mesh, so it would serve on one chip against several chips'
    peaks."""
    if chips != 1:
        raise ValueError(f"the harness builds one-chip engines only; a cell "
                         f"on {chips} chips needs a mesh from its config")
    cfg = model_config(config)
    serve = config["serve"]
    model = build_model(cfg)
    params = weights.make_params(model.init, seed)
    layer0, layer1 = pool_bytes_beside(params)
    geom = sched_mod.derive_page_geometry(
        cfg, serve["max_len"], layer0_bytes=layer0, layer1_bytes=layer1,
        kv_quant=kv_quant)
    engine = Engine(model, params, EngineConfig(
        max_len=serve["max_len"],
        prompt_pad_multiple=serve["prompt_pad_multiple"],
        eos_token=-1 if mix.get("ignore_eos") else 1))
    return Served(engine=engine, geom=geom, params=params)


def warm(served: Served, config: Dict, mix: Dict) -> None:
    """Compile every program the window can run: one admission per prompt
    bucket the mix can draw, the decode chunk, and both tier copies with
    the slot updates a restore makes."""
    serve, eng, geom = config["serve"], served.engine, served.geom
    buckets = traffic.prompt_buckets(mix, serve["prompt_pad_multiple"],
                                     serve["max_len"])
    n_slots = serve["n_slots"]
    for i in range(0, len(buckets), n_slots):
        sch = sched_mod.Scheduler(n_slots=n_slots, pages=geom)
        for b in buckets[i:i + n_slots]:
            sch.submit(np.full((b,), 2, np.int32), 2)
        eng.serve(scheduler=sch)
    pool, spill = eng._last_pool, eng._last_spill
    p_max = geom.max_pages_per_slot
    req = sched_mod.Request(rid=0, prompt=np.full((4,), 2, np.int32),
                            max_new_tokens=2, tokens=[2], pages=[0],
                            status=sched_mod.DECODING)
    spill = eng.pools.exec_spill(pool, spill, sched_mod.SpillAction(
        slot=0, req=req, src_pages=[0], dst_pages=[0], seat=0), p_max)
    pool = eng.pools.exec_restore(pool, spill, sched_mod.RestoreAction(
        slot=0, req=req, src_pages=[0], seat=0), p_max)
    jax.block_until_ready((pool, spill))
    eng._last_pool = eng._last_spill = None
    del pool, spill


# ------------------------------------------------------------------ check
def sample(gate: Gate, seed: int, want_tokens: int, cap: int = 24):
    """Finished requests to check, drawn from the seed: the one that
    served the most tokens and the longest one, then others until
    ``want_tokens`` served tokens are covered."""
    done = [r for r in gate.requests.values()
            if r.status == sched_mod.DRAINED and r.tokens]
    if not done:
        return []
    rng = np.random.default_rng(seed)
    picks = {max(done, key=lambda r: len(r.tokens)).rid,
             max(done, key=lambda r: r.prompt_len + len(r.tokens)).rid}
    rest = [r.rid for r in done if r.rid not in picks]
    rng.shuffle(rest)
    out = [gate.requests[i] for i in sorted(picks)]
    for rid in rest:
        if sum(len(r.tokens) for r in out) >= want_tokens or len(out) >= cap:
            break
        out.append(gate.requests[rid])
    return [(np.asarray(r.prompt, np.int32), np.asarray(r.tokens, np.int32))
            for r in out]


# --------------------------------------------------------------------- run
@dataclasses.dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, Dict[str, Any]]
    device: Dict[str, Any]
    breakdown: Optional[Dict[str, Any]]
    checks: Dict[str, Dict[str, float]]
    notes: List[str]
    gaps: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)   # summaries of the program's (and control's) gaps

    def line(self) -> str:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["checks"] = self.checks
        return json.dumps(out)


def run(*, spec: Dict, cell: Dict, config: Dict, mix: Dict, seed: int,
        seconds: float, trace: bool, device: Dict, t_start: float,
        compiles: CompileLog, control: bool = False,
        kv_quant: Optional[str] = None,
        readers: Optional[Dict[str, Callable]] = None,
        keep_trace: Optional[Path] = None,
        log: Callable[[str], None] = print) -> Result:
    """One measured run; ``device`` is what :func:`run.device_check`
    found. ``control`` judges the fp8 reference's picks in the program's
    place (the program's own gaps go to the notes); ``kv_quant`` serves
    with the program's quantized KV pages. A run with a compile inside the
    window is not correct."""
    served = build(config, mix, seed, kv_quant, chips=cell["chips"])
    warm(served, config, mix)
    shape, serve = config["model"], config["serve"]
    tracer = Tracer(min(TRACE_SECONDS, seconds)) if trace else None
    gate = Gate(n_slots=serve["n_slots"], pages=served.geom,
                arrivals=traffic.arrivals(mix, seed, shape["vocab_size"]),
                warm_in_s=mix["warm_in_s"], seconds=seconds,
                drain_cap_s=mix.get("drain_cap_s", 0.0),
                backlog=mix["arrival"] == "backlog", on_drain=tracer)
    try:
        served.engine.serve(scheduler=gate)
    finally:
        if tracer is not None:
            tracer.stop(gate)
    setup_s = gate.t_open - t_start
    n_compiles = compiles.between(gate.t_open, gate.t_close)
    log(f"compiles in window: {n_compiles} (device {device['kind']} "
        f"x{device['count']})")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:cell["chips"]])
    served.engine._last_pool = served.engine._last_spill = None
    tr = tracer.read(keep_trace) if tracer is not None else None

    # ---- outputs against the plain reference; the control puts the fp8
    # reference in the program's place, and its picks are judged instead
    limit = config["check"]["logit_gap_limit"]
    picks = sample(gate, seed, mix.get("check_tokens", 256))
    served_g, control_g = reference.token_gaps(
        shape, served.params, picks, n_out=mix["output"]["max"],
        control=control)
    judged = reference.summary(control_g if control else served_g)
    checks = {"logit_gap": {"value": judged["widest"], "limit": limit},
              "compiles_in_window": {"value": n_compiles, "limit": 0}}
    correct = (bool(picks) and judged["widest"] <= limit
               and n_compiles == 0)
    notes = [f"checked {len(picks)} requests, {len(served_g)} served "
             f"tokens: program {reference.summary(served_g)}"]
    gaps = {"program": reference.summary(served_g)}
    if control:
        gaps["control"] = judged
        notes.append(f"control (fp8 reference) {judged}")

    # ---- metrics
    r = readings.Readings(gate=gate, seconds=seconds, shape=shape,
                          n_slots=serve["n_slots"],
                          sync_interval=served.engine.ecfg.sync_interval,
                          chips=cell["chips"], kind=device["kind"],
                          trace=tr, tracer=tracer, setup_s=setup_s)
    metrics = {}
    for m in metrics_for(spec, cell["name"], trace):
        value = (readers[m["name"]](r) if trace
                 else r.end_to_end(m["name"]))
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": int(peak)}
    breakdown = None
    if tr is not None:
        dev["busy_s"] = r.busy_s()
        dev["window_s"] = r.window_s()
        breakdown = r.breakdown()
    attempted, failed = r.attempted_failed()
    notes.append(f"window: {r.n_in_window()} requests due, "
                 f"{len(gate.boundaries)} boundaries in the run, gate slept "
                 f"{gate.wait_s:.3f} s, compile seconds {compiles.seconds:.3f}")
    return Result(correct=correct, attempted=attempted, failed=failed,
                  metrics=metrics, device=dev, breakdown=breakdown,
                  checks=checks, notes=notes, gaps=gaps)
