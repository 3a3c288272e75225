"""One run of one cell: build the engine from the seed, warm every shape
the cell's traffic uses, serve through the arrival gate for the window,
check the served tokens against the plain reference, and reduce what was
seen to the cell's metrics.

``run.py`` is the command; this module holds the run so that the tests
can drive it without a chip.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import shutil
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.distributed import sharding as shd
from repro.models import build_model
from repro.serve import scheduler as sched_mod
from repro.serve.engine import Engine, EngineConfig
from repro.serve.pool import pool_bytes_beside

from bench import readings, reference, traffic, weights
from bench import trace as trace_mod
from bench.gate import Gate

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
FAMILIES_DIR = BENCH_DIR / "families"
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


def load_module(path: Path, prefix: str):
    """The module in the file ``path``, imported under a name of its own."""
    name = prefix + path.name[:-3].replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(name: str, directory: Path = FAMILIES_DIR):
    """The model family ``bench/families/<name>.py``: what a family file
    gives, and what it may give, is set out in ``bench/families/dense.py``."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no model family {name!r} ({path})")
    return load_module(path, "bench_family_")


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
    family: Any         # the configuration's module under bench/families/
    mesh: Any = None    # the configuration's mesh; None on one chip

    def scope(self):
        """The mesh as the ambient one, as the engine's own calls have it,
        so a program compiled here is the one the window calls."""
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())


def sharded_heads(config: Dict, family=None) -> Dict[str, int]:
    """The counts split over the ``model`` axis of a mesh, by what they
    count: the family's ``sharded_heads(config)`` where it gives one, else
    the query and KV heads of the model section."""
    if hasattr(family, "sharded_heads"):
        return family.sharded_heads(config)
    m = config["model"]
    return {"query heads": m["num_attention_heads"],
            "KV heads": m["num_key_value_heads"]}


def make_mesh(config: Dict, chips: int, family=None):
    """The mesh the configuration's ``serve.mesh`` names (``{"data": 1,
    "model": 4}``) over the first ``chips`` devices, or ``None`` where it
    names none. Refuses a mesh that is not the cell's chips, and heads that
    do not divide over its ``model`` axis (:func:`sharded_heads`)."""
    axes = config["serve"].get("mesh")
    if axes is None:
        if chips != 1:
            raise ValueError(f"a cell on {chips} chips needs a mesh in its "
                             f"configuration's serve section")
        return None
    size = math.prod(axes.values())
    if size != chips:
        raise ValueError(f"mesh {axes} spans {size} devices; the cell asks "
                         f"for {chips} chips")
    model = axes.get(shd.MODEL_AXIS, 1)
    for kind, n in sharded_heads(config, family).items():
        if n % model:
            raise ValueError(f"{n} {kind} do not divide over the "
                             f"{model}-way model axis of mesh {axes}")
    return shd.make_mesh(tuple(axes.values()), tuple(axes),
                         devices=jax.devices()[:chips])


def build(config: Dict, mix: Dict, seed: int,
          kv_quant: Optional[str] = None, chips: int = 1,
          families: Path = FAMILIES_DIR) -> Served:
    """Weights from the seed, the pool beside them, and the engine, as the
    configuration's deployment builds them: on one chip, or tensor-parallel
    over its mesh with the weights made straight into their shardings and
    the pool sized per shard. ``kv_quant`` switches on the program's own
    quantized KV pages (the control of ``bench/control.py``; the cells
    serve bf16 pages)."""
    family = load_family(config["family"], families)
    mesh = make_mesh(config, chips, family)
    cfg = family.model_config(config)
    serve = config["serve"]
    model = build_model(cfg)
    place = None
    if mesh is not None:
        shapes = jax.eval_shape(model.init, weights.seed_key(seed))
        place = shd.named_shardings(shapes, mesh)
    params = weights.make_params(model.init, seed, out_shardings=place,
                                 family=family)
    layer0, layer1 = pool_bytes_beside(params)
    geom = sched_mod.derive_page_geometry(
        cfg, serve["max_len"], layer0_bytes=layer0, layer1_bytes=layer1,
        model_shards=1 if mesh is None else mesh.shape[shd.MODEL_AXIS],
        kv_quant=kv_quant)
    engine = Engine(model, params, EngineConfig(
        max_len=serve["max_len"],
        prompt_pad_multiple=serve["prompt_pad_multiple"],
        eos_token=-1 if mix.get("ignore_eos") else 1, mesh=mesh))
    return Served(engine=engine, geom=geom, params=params, family=family,
                  mesh=mesh)


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
    with served.scope():
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
        keep_trace: Optional[Path] = None, families: Path = FAMILIES_DIR,
        log: Callable[[str], None] = print) -> Result:
    """One measured run; ``device`` is what :func:`run.device_check`
    found. ``control`` judges the fp8 reference's picks in the program's
    place (the program's own gaps go to the notes); ``kv_quant`` serves
    with the program's quantized KV pages. A run with a compile inside the
    window is not correct."""
    served = build(config, mix, seed, kv_quant, chips=cell["chips"],
                   families=families)
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
    t_check = time.perf_counter()
    served_g, control_g = served.family.token_gaps(
        shape, served.params, picks, n_out=mix["output"]["max"],
        control=control)
    check_s = time.perf_counter() - t_check
    judged = reference.summary(control_g if control else served_g)
    checks = {"logit_gap": {"value": judged["widest"], "limit": limit},
              "compiles_in_window": {"value": n_compiles, "limit": 0}}
    correct = (bool(picks) and judged["widest"] <= limit
               and n_compiles == 0)
    notes = [f"checked {len(picks)} requests, {len(served_g)} served "
             f"tokens in {check_s:.1f} s: program "
             f"{reference.summary(served_g)}"]
    gaps = {"program": reference.summary(served_g)}
    if control:
        gaps["control"] = judged
        notes.append(f"control (fp8 reference) {judged}")

    # ---- metrics
    r = readings.Readings(gate=gate, seconds=seconds, shape=shape,
                          n_slots=serve["n_slots"],
                          sync_interval=served.engine.ecfg.sync_interval,
                          chips=cell["chips"], kind=device["kind"],
                          trace=tr, tracer=tracer, setup_s=setup_s,
                          family=served.family)
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
    notes.append(f"pool: {gate.page_pool.high_water} of "
                 f"{served.geom.n_pages - 1} pages at most, "
                 f"{gate.preemptions} spills, {gate.restores} restores, "
                 f"{sum(x.rejected for x in gate.records.values())} rejected")
    return Result(correct=correct, attempted=attempted, failed=failed,
                  metrics=metrics, device=dev, breakdown=breakdown,
                  checks=checks, notes=notes, gaps=gaps)
