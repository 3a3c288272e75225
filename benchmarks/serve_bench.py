"""Serving benchmark: static vs continuous vs paged-two-tier vs
prefix-shared tokens/s AND pool footprint.

Drives the same synthetic mixed short/long request stream through the same
Engine in up to four modes:

  * **static** — requests are grouped into fixed batches of ``n_slots``; a
    batch admits once and decodes until its SLOWEST request drains (empty
    slots idle — the classic straggler cost).
  * **continuous** — one scheduler over the whole stream; drained slots are
    refilled from the queue at every drain boundary. Dense pool: every slot
    reserves a ``max_len``-deep KV slab.
  * **paged** (``--paged``) — the paged two-tier pool inside the SAME
    layer-0 byte budget the dense pool used: admission by pages, spill to
    the layer-1 tier under pressure. The interesting number is not just
    tok/s but *concurrent slots per byte* — the capacity win the paper gets
    from stacking a second memory layer.
  * **paged+share** (``--prefix-share``) — the stream becomes the
    shared-system-prompt workload (one common ``--system-len`` prefix per
    request) and the paged pool runs twice in the SAME layer-0 byte
    budget, sharing off vs on. Reported head-to-head: tok/s, TTFT
    percentiles, physical vs *mapped* pages (the concurrent-residency
    win), plus a bit-identical output check between the two runs.

A third head-to-head, ``--speculate``, measures self-drafting speculative
decoding (DESIGN.md §Speculative decoding): the repetitive (motif-tiled)
stream runs through the paged pool twice in the SAME layer-0 byte budget,
speculation off vs on. The decode win is reported as **tokens per decode
forward**: on the modeled memory-bound target every decode forward
streams the slot pool's entire resident KV through layer 0, so tokens per
full-pool sweep IS decode throughput — host wall-clock on the CPU test
backend is FLOP-bound (a width-(k+1) verify costs ~k× a single-token
step there) and is reported honestly alongside, not gated on.
``--require-speculate-win`` gates on >=1.5x tokens-per-forward and
bit-identical outputs vs the non-speculative run.

A separate head-to-head, ``--chunked-prefill``, measures the admission
stall chunked prefill exists to kill (DESIGN.md §Chunked prefill). Three
runs over the same short-request stream: **baseline** (no long prompt),
**unchunked** (+one ``--long-prompt-len`` prompt admitted whole — the
stall), **chunked** (+the same prompt admitted ``--chunk-prefill-tokens``
per boundary, interleaved with decode). The latency metric is the
token-weighted inter-token distribution: each token emitted at a drain
boundary contributes one sample of that boundary's wall / sync_interval.
``--require-flat-p99`` gates on chunked p99 staying within
``--flat-p99-tol`` of baseline WHILE the one-shot run degrades past it,
and the chunked outputs must be bit-identical to the one-shot outputs.
A phase-timed pass adds the prefill/insert/generate/drain breakdown.

``--mesh N`` (N > 1) runs the mesh-sharded head-to-head (DESIGN.md
§Sharded serving): the same paged stream served single-device and under
an N-way mesh with tensor-parallel weights, head-axis KV page placement
and per-shard pool budgets. The gated metric is **modeled decode
scaling**: emitted tokens per decode forward divided by the per-shard
resident-KV bytes that forward sweeps — on the modeled memory-bound
target the sweep IS the forward's cost, so the ratio is decode
throughput scaling. Host wall tok/s is reported alongside but not gated
(the CPU test backend is FLOP-bound and re-runs the full FLOPs on every
host device). ``--require-scaling`` gates on >=1.7x modeled scaling,
outputs bit-exact against the same engine's one-shot rollout, and one
host sync per drain boundary on BOTH sides — sharding must not add
sync points.

``--disaggregate`` runs the prefill/decode role-split head-to-head
(DESIGN.md §Disaggregated serving): mixed traffic — a short-request decode
stream plus several long prompts admitted in chunks — served twice in the
SAME layer-0 byte budget, combined engine vs disaggregated roles. The
gated metric is **decode-role tokens/s**: decode tokens over the wall the
decode clock actually spans. Combined, that clock is the full boundary
(prefill chunks ride the decode engine's dispatch stream, so every decode
consumer observes the prompt work); disaggregated, it is the decode
role's own dispatch + drain (both runs phase-timed so each phase blocks on
its device work — the role split is measured, not simulated). The same
split drives the token-weighted inter-token p99: ``--require-disagg-win``
gates on decode tok/s >= ``--disagg-win-min`` x combined AT a p99 no worse
than ``1 + --flat-p99-tol`` x combined, bit-identical outputs, and at most
one host sync per role per boundary.

``--kv-quant`` runs the tier-codec head-to-head (DESIGN.md §Tiered KV
compression & host parking): the same stream served twice in the SAME
layer-0 byte budget, fp16 pages vs the quantized codec (int8 or fp8 —
either spills at int8). A smaller page prices more pages into the budget,
so the gated metric is **concurrent resident sessions per layer-0 byte**:
``--require-residency-win`` gates on the quantized run holding >=1.8x the
fp16 run's resident high water at the same bytes, with every request
draining and the greedy FIRST token agreeing with the fp16 run on >=75%
of the stream (full-sequence identity is not gated — lossy codecs may
legitimately flip a late argmax). ``--park-idle N`` additionally runs the
layer-2 host tier inside the quantized serve: after N decode steps every
decoding resident parks to a host blob, resumes, and the stream completes
— park counters land in the record.

Every record carries pool bytes and pages-in-use next to throughput, so the
dense-vs-paged comparison shows capacity, not just speed. Emits
``benchmarks/artifacts/serve_bench.json``; ``--emit-bench`` additionally
writes the flat cross-PR metric file ``BENCH_10.json`` at the repo root
(diffed by ``tools/diff_bench.py``).

    PYTHONPATH=src python -m benchmarks.serve_bench [--target NAME] [--paged]
        [--page-tokens N] [--layer0-bytes B] [--layer1-bytes B]
        [--require-spill] [--prefix-share] [--system-len N]
        [--require-share-win] [--chunked-prefill] [--long-prompt-len N]
        [--chunk-prefill-tokens N] [--sync-interval N] [--require-flat-p99]
        [--flat-p99-tol F] [--speculate] [--speculate-tokens K]
        [--require-speculate-win] [--mesh SPEC] [--mesh-axes NAMES]
        [--require-scaling] [--disaggregate] [--require-disagg-win]
        [--disagg-win-min F] [--kv-quant CODEC] [--park-idle N]
        [--require-residency-win] [--emit-bench] [...]
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Dict, List, Optional

from benchmarks.common import add_target_arg, fmt_table, save_artifact, \
    target_scope
from repro.launch.device import enable_compile_cache

BENCH_ID = 10


def _emit_bench_json(meta: Dict, metrics: Dict) -> str:
    """Write the flat cross-PR metric file ``BENCH_<id>.json`` at the repo
    root. Values are plain numbers only, keyed ``<run>.<metric>``, so
    ``tools/diff_bench.py`` can diff any two PRs' files key by key."""
    import json
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / \
        f"BENCH_{BENCH_ID}.json"
    clean = {k: v for k, v in metrics.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)}
    payload = {"bench_id": BENCH_ID, "schema": 1, "meta": meta,
               "metrics": clean}
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    return str(path)


def _run_mode(engine, stream: List[Dict], n_slots: int, mode: str,
              geom=None) -> Dict:
    from repro.serve.scheduler import Scheduler, percentile

    paged = mode in ("paged", "paged+share")

    def make_sched():
        return Scheduler(n_slots=n_slots, pages=geom if paged else None,
                         prefix_share=(mode == "paged+share"))

    t0 = time.monotonic()
    reports = []
    if mode == "static":                    # one batch at a time
        for i in range(0, len(stream), n_slots):
            sch = make_sched()
            for spec in stream[i:i + n_slots]:
                sch.submit(spec["prompt"], spec["max_new_tokens"])
            reports.append(engine.serve(scheduler=sch))
    else:                                   # continuous / paged [+share]
        sch = make_sched()
        for spec in stream:
            sch.submit(spec["prompt"], spec["max_new_tokens"])
        reports.append(engine.serve(scheduler=sch))
    dt = time.monotonic() - t0
    n_tokens = sum(len(r.tokens) for rep in reports for r in rep.requests)
    ttft = [t for rep in reports for t in rep.stats["ttft_steps"]]
    ttft_emit = [t for rep in reports
                 for t in rep.stats["ttft_emit_steps"]]
    last = reports[-1].stats
    rec = {
        "mode": mode,
        "wall_s": dt,
        "n_tokens": n_tokens,
        "tok_per_s": n_tokens / dt if dt else 0.0,
        "decode_steps": sum(rep.stats["decode_steps"] for rep in reports),
        "host_syncs": sum(rep.stats["host_syncs"] for rep in reports),
        "max_slot_reuse": max(rep.stats["max_slot_reuse"]
                              for rep in reports),
        "completed": sum(rep.stats["drained"] for rep in reports),
        "n_slots": n_slots,
        "preemptions": sum(rep.stats["preemptions"] for rep in reports),
        "spilled_pages": sum(rep.stats["spilled_pages"] for rep in reports),
        "restores": sum(rep.stats["restores"] for rep in reports),
        # admission wait in decode-step clock units (scheduler TTFT).
        # Meaningless for static mode: each per-batch serve() restarts the
        # step clock, so cross-batch queueing is invisible — reported as
        # None and rendered "-" in the table.
        "ttft_steps_p50": (None if mode == "static"
                           else percentile(ttft, 50)),
        "ttft_steps_p95": (None if mode == "static"
                           else percentile(ttft, 95)),
        # first-token EMISSION boundary in step-clock units — the real
        # TTFT (admission-wait alone reads 0 whenever the stream admits
        # at the first boundary, which is what BENCH_7 reported)
        "ttft_emit_p50": (None if mode == "static"
                          else percentile(ttft_emit, 50)),
        "ttft_emit_p95": (None if mode == "static"
                          else percentile(ttft_emit, 95)),
        # rid -> tokens, for cross-mode bit-identity checks (single-report
        # modes only: static restarts rids per batch)
        "outputs": ({r.rid: list(r.tokens) for r in reports[0].requests}
                    if len(reports) == 1 else {}),
    }
    if paged:
        rec.update({
            "pool_bytes": last["pool_bytes"],
            "spill_bytes": last["spill_bytes"],
            "page_tokens": last["page_tokens"],
            "n_pages": last["n_pages"],
            "pages_high_water": max(rep.stats["pages_high_water"]
                                    for rep in reports),
            "spill_high_water": max(rep.stats["spill_high_water"]
                                    for rep in reports),
            "mapped_high_water": max(rep.stats["mapped_high_water"]
                                     for rep in reports),
        })
    if mode == "paged+share":
        rec.update({k: last[k] for k in (
            "prefix_hits", "prefix_misses", "shared_prefix_tokens",
            "cow_copies")})
        rec["residency_ratio"] = (rec["mapped_high_water"]
                                  / max(rec["pages_high_water"], 1))
    return rec


def run(target_name=None, arch: str = "qwen2.5-3b", n_requests: int = 32,
        prompt_len: int = 16, gen_len: int = 12, n_slots: int = None,
        seed: int = 0, paged: bool = False, page_tokens: int = 8,
        layer0_bytes: Optional[int] = None,
        layer1_bytes: Optional[int] = None, max_slots: int = 32,
        require_spill: bool = False, prefix_share: bool = False,
        system_len: Optional[int] = None,
        require_share_win: bool = False,
        sync_interval: Optional[int] = None,
        emit_bench: bool = False) -> str:
    import jax
    from repro.configs import get_reduced
    from repro.core.target import get_target
    from repro.models import build_model
    from repro.serve.engine import Engine, EngineConfig
    from repro.serve.scheduler import (derive_n_slots, derive_page_geometry,
                                       kv_bytes_per_token,
                                       shared_prefix_stream, synthetic_stream)

    paged = paged or prefix_share
    with target_scope(target_name):
        target = get_target()
        cfg = get_reduced(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        if prefix_share:
            # shared-system-prompt workload: one common prefix (3 full
            # pages by default) + unique tails up to one page
            system_len = system_len or 3 * page_tokens
            tail_len = page_tokens
            prompt_len = system_len + tail_len
            stream = shared_prefix_stream(n_requests, system_len, tail_len,
                                          gen_len, cfg.vocab_size, seed)
        else:
            stream = synthetic_stream(n_requests, prompt_len, gen_len,
                                      cfg.vocab_size, seed)
        max_len = prompt_len + gen_len
        n_slots = n_slots or derive_n_slots(cfg, max_len, max_slots=8)
        engine = Engine(model, params,
                        EngineConfig(max_len=max_len,
                                     sync_interval=sync_interval or 4))
        # the dense pool's layer-0 footprint is the shared byte budget:
        # the paged pool must beat it on concurrency INSIDE the same bytes
        dense_bytes = n_slots * kv_bytes_per_token(cfg) * max_len
        modes = [("static", n_slots, None), ("continuous", n_slots, None)]
        geom = None
        if paged:
            geom = derive_page_geometry(
                cfg, max_len, page_tokens=page_tokens, max_slots=max_slots,
                layer0_bytes=(layer0_bytes if layer0_bytes is not None
                              else dense_bytes),
                layer1_bytes=layer1_bytes)
            paged_slots = derive_n_slots(cfg, max_len, pages=geom,
                                         max_slots=max_slots)
            modes.append(("paged", paged_slots, geom))
            if prefix_share:
                # sharing on vs off, SAME geometry and layer-0 bytes
                modes.append(("paged+share", paged_slots, geom))
        # warmup: compile prefill (per distinct prompt length) + decode chunk
        for mode, slots, g in modes[1:]:
            _run_mode(engine, stream, slots, mode, g)
        recs = [_run_mode(engine, stream, slots, mode, g)
                for mode, slots, g in modes]

    by_mode = {r["mode"]: r for r in recs}
    outputs = {r["mode"]: r.pop("outputs") for r in recs}   # not in artifact
    stat, cont = by_mode["static"], by_mode["continuous"]
    for r in recs:
        r["pool_bytes"] = r.get("pool_bytes", dense_bytes)
    speedup = (cont["tok_per_s"] / stat["tok_per_s"]
               if stat["tok_per_s"] else 0.0)
    artifact = {
        "arch": cfg.name, "target": target.name, "n_requests": n_requests,
        "prompt_len": prompt_len, "gen_len": gen_len, "n_slots": n_slots,
        "dense_pool_bytes": dense_bytes,
        "static": stat, "continuous": cont, "speedup_tok_per_s": speedup,
    }
    lines = []
    if paged:
        pg = by_mode["paged"]
        slots_ratio = pg["n_slots"] / max(cont["n_slots"], 1)
        artifact.update({
            "paged": pg,
            "slots_ratio_paged_vs_dense": slots_ratio,
            "layer0_bytes": pg["pool_bytes"],
            "layer1_bytes": pg["spill_bytes"],
        })
        lines.append(
            f"paged vs dense concurrency: {pg['n_slots']} vs "
            f"{cont['n_slots']} slots in {pg['pool_bytes']} layer-0 bytes "
            f"({slots_ratio:.2f}x), spill tier: {pg['preemptions']} "
            f"preemptions / {pg['spilled_pages']} pages")
        if require_spill and pg["preemptions"] < 1:
            raise SystemExit(
                "serve_bench --require-spill: the layer-1 spill tier was "
                "never exercised — shrink --layer0-bytes")
    if prefix_share:
        pg, sh = by_mode["paged"], by_mode["paged+share"]
        if outputs["paged"] != outputs["paged+share"]:
            raise SystemExit(
                "serve_bench --prefix-share: sharing-on outputs differ "
                "from sharing-off — prefix sharing must be bit-exact")
        artifact.update({
            "prefix_share": sh, "system_len": system_len,
            "residency_ratio": sh["residency_ratio"],
            "share_outputs_bit_identical": True,
        })
        lines.append(
            f"prefix sharing (system prompt {system_len} tok, same "
            f"{sh['pool_bytes']} layer-0 bytes): residency "
            f"{sh['mapped_high_water']} mapped vs {sh['pages_high_water']} "
            f"physical pages ({sh['residency_ratio']:.2f}x), ttft p50/p95 "
            f"{sh['ttft_steps_p50']:.0f}/{sh['ttft_steps_p95']:.0f} vs "
            f"{pg['ttft_steps_p50']:.0f}/{pg['ttft_steps_p95']:.0f} steps "
            f"sharing-off, {sh['prefix_hits']} hits "
            f"({sh['shared_prefix_tokens']} prompt tokens from cache, "
            f"{sh['cow_copies']} COW), outputs bit-identical")
        if require_share_win and (
                sh["residency_ratio"] < 1.5
                or sh["ttft_steps_p95"] > pg["ttft_steps_p95"]):
            raise SystemExit(
                "serve_bench --require-share-win: expected >=1.5x mapped/"
                "physical residency and no-worse TTFT p95 with sharing on; "
                f"got {sh['residency_ratio']:.2f}x, p95 "
                f"{sh['ttft_steps_p95']:.0f} vs {pg['ttft_steps_p95']:.0f}")
    save_artifact("serve_bench.json", artifact)
    if emit_bench:
        metrics = {"speedup_tok_per_s": speedup}
        for r in recs:
            metrics.update({f"{r['mode']}.{k}": v for k, v in r.items()})
        path = _emit_bench_json(
            {"mode": "serve", "arch": cfg.name, "target": target.name,
             "n_requests": n_requests}, metrics)
        lines.append(f"bench metrics -> {path}")
    rows = [[r["mode"], f"{r['tok_per_s']:.1f}", r["n_tokens"], r["n_slots"],
             r["pool_bytes"], r.get("pages_high_water", "-"),
             ("-" if r["ttft_steps_p50"] is None else
              f"{r['ttft_steps_p50']:.0f}/{r['ttft_steps_p95']:.0f}"),
             r["preemptions"], r["max_slot_reuse"],
             f"{r['wall_s']*1e3:.0f} ms"] for r in recs]
    table = fmt_table(
        ["mode", "tok/s", "tokens", "slots", "pool bytes", "pages hw",
         "ttft p50/95", "preempt", "max reuse", "wall"],
        rows, title=f"Serve bench — {cfg.name}, {n_requests} requests "
                    f"({target.name})")
    return "\n".join([table,
                      f"continuous/static speedup: {speedup:.2f}x"] + lines)


def _stream_metrics(rep, sync_interval: int) -> Dict:
    """Flatten one ServeReport into the latency/counter record the
    chunked-prefill head-to-head compares across runs.

    The inter-token distribution is token-weighted: every token emitted at
    a drain boundary contributes one sample of that boundary's
    ``wall / sync_interval``, so a slow boundary counts once per consumer
    that observed the gap. Boundaries that emit nothing (all decode slots
    drained, only prefill chunks ran) add no samples — no stream observed
    an inter-token gap there.
    """
    from repro.serve.scheduler import percentile

    st = rep.stats
    samples: List[float] = []
    for w, t in zip(st["boundary_wall_s"], st["boundary_tokens"]):
        samples.extend([w / sync_interval] * t)
    return {
        "n_tokens": sum(len(r.tokens) for r in rep.requests),
        "intertoken_p50_ms": percentile(samples, 50) * 1e3,
        "intertoken_p95_ms": percentile(samples, 95) * 1e3,
        "intertoken_p99_ms": percentile(samples, 99) * 1e3,
        "ttft_steps_p50": percentile(st["ttft_steps"], 50),
        "ttft_steps_p95": percentile(st["ttft_steps"], 95),
        "ttft_emit_p50": percentile(st["ttft_emit_steps"], 50),
        "ttft_emit_p95": percentile(st["ttft_emit_steps"], 95),
        "e2e_steps_p50": percentile(st["e2e_steps"], 50),
        "e2e_steps_p95": percentile(st["e2e_steps"], 95),
        "boundaries": len(st["boundary_wall_s"]),
        "decode_steps": st["decode_steps"],
        "host_syncs": st["host_syncs"],
        "preemptions": st["preemptions"],
        "spilled_pages": st["spilled_pages"],
        "restores": st["restores"],
        "prefill_chunks": st["prefill_chunks"],
        "max_boundary_prefill_tokens": st["max_boundary_prefill_tokens"],
        "pages_high_water": st.get("pages_high_water", 0),
        "mapped_high_water": st.get("mapped_high_water", 0),
        "prefix_hits": st.get("prefix_hits", 0),
        "cow_copies": st.get("cow_copies", 0),
    }


def run_chunked(target_name=None, arch: str = "qwen2.5-3b",
                n_requests: int = 32, prompt_len: int = 16,
                gen_len: int = 12, n_slots: Optional[int] = None,
                seed: int = 0, page_tokens: int = 8,
                layer0_bytes: Optional[int] = None,
                layer1_bytes: Optional[int] = None, max_slots: int = 32,
                prefix_share: bool = False,
                system_len: Optional[int] = None,
                long_prompt_len: int = 4096, long_gen_len: int = 4,
                chunk_prefill_tokens: int = 0, sync_interval: int = 8,
                flat_p99_tol: float = 0.10, require_flat_p99: bool = False,
                require_spill: bool = False, repeats: int = 3,
                emit_bench: bool = False) -> str:
    """The chunked-prefill admission-stall head-to-head (see module doc)."""
    import jax
    import numpy as np
    from repro.configs import get_reduced
    from repro.core.target import get_target
    from repro.models import build_model
    from repro.serve.engine import Engine, EngineConfig
    from repro.serve.scheduler import (Scheduler, derive_page_geometry,
                                       derive_prefill_chunk,
                                       kv_bytes_per_token,
                                       shared_prefix_stream, synthetic_stream)

    with target_scope(target_name):
        target = get_target()
        cfg = get_reduced(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        if prefix_share:
            system_len = system_len or 3 * page_tokens
            prompt_len = system_len + page_tokens
            shorts = shared_prefix_stream(n_requests, system_len,
                                          page_tokens, gen_len,
                                          cfg.vocab_size, seed)
        else:
            shorts = synthetic_stream(n_requests, prompt_len, gen_len,
                                      cfg.vocab_size, seed)
        rng = np.random.RandomState(seed + 1)
        long_prompt = rng.randint(2, cfg.vocab_size,
                                  size=long_prompt_len).astype(np.int32)
        chunk = chunk_prefill_tokens or derive_prefill_chunk(cfg)
        max_len = long_prompt_len + max(gen_len, long_gen_len)
        n_slots = n_slots or 8
        if layer0_bytes is None:
            # fully resident by default: the head-to-head isolates the
            # admission stall. --layer0-bytes shrinks the pool to compose
            # chunking with spill/preemption (CI runs both).
            resident = (n_slots * (prompt_len + gen_len + page_tokens)
                        + long_prompt_len + long_gen_len + page_tokens)
            layer0_bytes = kv_bytes_per_token(cfg) * resident
        geom = derive_page_geometry(cfg, max_len, page_tokens=page_tokens,
                                    max_slots=max_slots,
                                    layer0_bytes=layer0_bytes,
                                    layer1_bytes=layer1_bytes)
        engine = Engine(model, params,
                        EngineConfig(max_len=max_len,
                                     sync_interval=sync_interval))

        def serve(with_long, chunk_setting):
            sch = Scheduler(n_slots=n_slots, pages=geom,
                            prefix_share=prefix_share,
                            chunk_prefill_tokens=chunk_setting)
            stream = list(shorts)
            if with_long:
                # lands mid-stream: the pool decodes at full concurrency
                # when the long prompt admits
                stream.insert(min(n_slots, len(stream)),
                              {"prompt": long_prompt,
                               "max_new_tokens": long_gen_len})
            for spec in stream:
                sch.submit(spec["prompt"], spec["max_new_tokens"])
            t0 = time.monotonic()
            rep = engine.serve(scheduler=sch)
            return rep, time.monotonic() - t0

        runs = [("baseline", False, chunk),   # no long prompt
                ("unchunked", True, None),    # one-shot 4k admission: stall
                ("chunked", True, chunk)]     # chunked 4k admission
        for _, with_long, c in runs:          # warmup: compile everything
            serve(with_long, c)
        recs, outputs = [], {}
        for name, with_long, c in runs:
            # wall-clock p99 on a shared host is noisy: measure `repeats`
            # passes and keep the median-p99 one
            passes = []
            for _ in range(max(1, repeats)):
                rep, dt = serve(with_long, c)
                m = {"run": name, "wall_s": dt,
                     **_stream_metrics(rep, sync_interval)}
                m["tok_per_s"] = m["n_tokens"] / dt if dt else 0.0
                passes.append((m, rep))
            passes.sort(key=lambda p: p[0]["intertoken_p99_ms"])
            rec, rep = passes[len(passes) // 2]
            recs.append(rec)
            outputs[name] = {r.rid: list(r.tokens) for r in rep.requests}
        # phase breakdown runs separately: phase_timing blocks on device
        # completion per phase, which would skew the latency numbers above
        phases = {}
        engine.ecfg.phase_timing = True
        try:
            for name, with_long, c in (("unchunked", True, None),
                                       ("chunked", True, chunk)):
                rep, _ = serve(with_long, c)
                phases[name] = dict(rep.stats.get("phase_s", {}))
        finally:
            engine.ecfg.phase_timing = False

    by = {r["run"]: r for r in recs}
    if outputs["unchunked"] != outputs["chunked"]:
        raise SystemExit(
            "serve_bench --chunked-prefill: chunked outputs differ from "
            "one-shot prefill — chunked prefill must be bit-exact")
    base_p99 = by["baseline"]["intertoken_p99_ms"] or 1e-9
    ratio_chunked = by["chunked"]["intertoken_p99_ms"] / base_p99
    ratio_unchunked = by["unchunked"]["intertoken_p99_ms"] / base_p99
    artifact = {
        "arch": cfg.name, "target": target.name, "n_requests": n_requests,
        "long_prompt_len": long_prompt_len,
        "chunk_prefill_tokens": chunk, "sync_interval": sync_interval,
        "n_slots": n_slots, "layer0_bytes": layer0_bytes,
        "prefix_share": prefix_share,
        "p99_ratio_chunked": ratio_chunked,
        "p99_ratio_unchunked": ratio_unchunked,
        "flat_p99_tol": flat_p99_tol,
        "outputs_bit_identical": True,
        "phase_s": phases,
        "runs": {r["run"]: r for r in recs},
    }
    save_artifact("serve_chunked_bench.json", artifact)
    rows = [[r["run"], f"{r['tok_per_s']:.1f}", r["n_tokens"],
             f"{r['intertoken_p50_ms']:.1f}",
             f"{r['intertoken_p99_ms']:.1f}",
             f"{r['ttft_emit_p50']:.0f}/{r['ttft_emit_p95']:.0f}",
             f"{r['e2e_steps_p95']:.0f}", r["preemptions"],
             r["prefill_chunks"], f"{r['wall_s']*1e3:.0f} ms"]
            for r in recs]
    table = fmt_table(
        ["run", "tok/s", "tokens", "it p50 ms", "it p99 ms",
         "ttft emit 50/95", "e2e p95", "preempt", "chunks", "wall"],
        rows, title=f"Chunked prefill head-to-head — {cfg.name}, "
                    f"{n_requests}+1 requests, {long_prompt_len}-token "
                    f"admission, chunk={chunk} ({target.name})")
    phase_keys = ("prefill", "insert", "generate", "drain")
    phase_rows = [[name] + [f"{phases[name].get(k, 0.0)*1e3:.0f}"
                            for k in phase_keys]
                  for name in phases]
    phase_table = fmt_table(
        ["run"] + [f"{k} ms" for k in phase_keys], phase_rows,
        title="Phase breakdown (separate phase-timed pass)")
    lines = [
        table, phase_table,
        f"p99 inter-token vs baseline: chunked x{ratio_chunked:.2f}, "
        f"one-shot x{ratio_unchunked:.2f} (tol {flat_p99_tol:.0%}); "
        f"outputs bit-identical"]
    if require_spill and by["chunked"]["preemptions"] < 1:
        raise SystemExit(
            "serve_bench --require-spill: the chunked run never preempted "
            "— shrink --layer0-bytes")
    if emit_bench:
        metrics = {"p99_ratio_chunked": ratio_chunked,
                   "p99_ratio_unchunked": ratio_unchunked}
        for r in recs:
            metrics.update({f"{r['run']}.{k}": v for k, v in r.items()})
        for name, ph in phases.items():
            metrics.update({f"{name}.phase_{k}_s": v
                            for k, v in ph.items()})
        path = _emit_bench_json(
            {"mode": "chunked-prefill", "arch": cfg.name,
             "target": target.name, "n_requests": n_requests,
             "long_prompt_len": long_prompt_len,
             "chunk_prefill_tokens": chunk,
             "sync_interval": sync_interval}, metrics)
        lines.append(f"bench metrics -> {path}")
    if require_flat_p99:
        if ratio_chunked > 1 + flat_p99_tol:
            raise SystemExit(
                "serve_bench --require-flat-p99: chunked admission moved "
                f"p99 inter-token x{ratio_chunked:.2f} vs baseline "
                f"(tolerance {flat_p99_tol:.0%}) — the chunk budget is not "
                "hiding under decode; shrink --chunk-prefill-tokens or "
                "raise --sync-interval")
        if ratio_unchunked <= 1 + flat_p99_tol:
            raise SystemExit(
                "serve_bench --require-flat-p99: the one-shot admission "
                f"stall never materialized (x{ratio_unchunked:.2f}) — the "
                "head-to-head is not measuring anything; lengthen "
                "--long-prompt-len")
    return "\n".join(lines)


def run_speculate(target_name=None, arch: str = "qwen2.5-3b",
                  n_requests: int = 24, prompt_len: int = 48,
                  gen_len: int = 32, n_slots: int = None, seed: int = 0,
                  page_tokens: int = 8,
                  layer0_bytes: Optional[int] = None,
                  layer1_bytes: Optional[int] = None, max_slots: int = 32,
                  speculate_tokens: int = 0,
                  sync_interval: Optional[int] = None,
                  require_speculate_win: bool = False,
                  emit_bench: bool = False) -> str:
    """Speculative-decoding head-to-head: the repetitive stream through
    the paged pool in the SAME layer-0 byte budget, speculation off vs on.

    The gated metric is decode **tokens per forward**: each decode forward
    sweeps the pool's entire resident KV through layer 0 — the dominant
    cost on the modeled memory-bound target — so emitted tokens per sweep
    IS decode throughput there. Host wall tok/s is reported alongside but
    NOT gated: the CPU test backend is FLOP-bound, where a width-(k+1)
    verify forward genuinely costs ~k× a single-token step.
    """
    import jax
    from repro.configs import get_reduced
    from repro.core.target import get_target
    from repro.models import build_model
    from repro.serve.engine import Engine, EngineConfig
    from repro.serve.scheduler import (Scheduler, derive_n_slots,
                                       derive_page_geometry,
                                       derive_speculate_tokens,
                                       kv_bytes_per_token, percentile,
                                       repetitive_stream)

    with target_scope(target_name):
        target = get_target()
        cfg = get_reduced(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        k = speculate_tokens or derive_speculate_tokens(cfg) or 4
        stream = repetitive_stream(n_requests, prompt_len, gen_len,
                                   cfg.vocab_size, seed)
        max_len = prompt_len + gen_len
        n_slots = n_slots or derive_n_slots(cfg, max_len, max_slots=8)
        dense_bytes = n_slots * kv_bytes_per_token(cfg) * max_len
        geom = derive_page_geometry(
            cfg, max_len, page_tokens=page_tokens, max_slots=max_slots,
            layer0_bytes=(layer0_bytes if layer0_bytes is not None
                          else dense_bytes),
            layer1_bytes=layer1_bytes)
        slots = derive_n_slots(cfg, max_len, pages=geom,
                               max_slots=max_slots)
        engine = Engine(model, params,
                        EngineConfig(max_len=max_len,
                                     sync_interval=sync_interval or 4,
                                     speculate_tokens=k))

        def one(spec_k: int) -> Dict:
            engine.ecfg.speculate_tokens = spec_k
            sch = Scheduler(n_slots=slots, pages=geom)
            for spec in stream:
                sch.submit(spec["prompt"], spec["max_new_tokens"])
            t0 = time.monotonic()
            rep = engine.serve(scheduler=sch)
            dt = time.monotonic() - t0
            st = rep.stats
            n_tokens = sum(len(r.tokens) for r in rep.requests)
            rec = {
                "mode": "speculate" if spec_k else "baseline",
                "speculate_tokens": spec_k,
                "wall_s": dt,
                "n_tokens": n_tokens,
                "tok_per_s": n_tokens / dt if dt else 0.0,
                "decode_steps": st["decode_steps"],
                "host_syncs": st["host_syncs"],
                "completed": st["drained"],
                "n_slots": slots,
                "pool_bytes": st["pool_bytes"],
                "preemptions": st["preemptions"],
                # the gated metric: emitted tokens per full-pool KV sweep
                "tok_per_forward": (n_tokens / st["decode_steps"]
                                    if st["decode_steps"] else 0.0),
                "ttft_steps_p50": percentile(st["ttft_steps"], 50),
                "ttft_steps_p95": percentile(st["ttft_steps"], 95),
                # emission-boundary TTFT: non-zero even when every slot
                # admits at the first boundary (see _run_mode)
                "ttft_emit_p50": percentile(st["ttft_emit_steps"], 50),
                "ttft_emit_p95": percentile(st["ttft_emit_steps"], 95),
                "outputs": {r.rid: list(r.tokens) for r in rep.requests},
            }
            if spec_k:
                rec.update({key: st[key] for key in (
                    "spec_proposed", "spec_accepted", "spec_rejected",
                    "spec_acceptance_rate")})
            return rec

        for s in (0, k):        # warmup: compile both variants' chunks
            one(s)
        off, on = one(0), one(k)

    outputs = (off.pop("outputs"), on.pop("outputs"))
    identical = outputs[0] == outputs[1]
    ratio = (on["tok_per_forward"] / off["tok_per_forward"]
             if off["tok_per_forward"] else 0.0)
    artifact = {
        "arch": cfg.name, "target": target.name, "n_requests": n_requests,
        "prompt_len": prompt_len, "gen_len": gen_len,
        "speculate_tokens": k, "layer0_bytes": off["pool_bytes"],
        "baseline": off, "speculate": on,
        "tok_per_forward_ratio": ratio,
        "speculate_outputs_bit_identical": identical,
    }
    save_artifact("serve_speculate.json", artifact)
    lines = [
        f"speculative decoding (k={k}, {on['pool_bytes']} layer-0 bytes, "
        f"acceptance {on['spec_acceptance_rate']:.2f}: "
        f"{on['spec_accepted']}/{on['spec_proposed']} drafts): "
        f"{on['tok_per_forward']:.2f} vs {off['tok_per_forward']:.2f} "
        f"decode tokens/forward ({ratio:.2f}x), wall "
        f"{on['tok_per_s']:.1f} vs {off['tok_per_s']:.1f} tok/s, outputs "
        f"{'bit-identical' if identical else 'DIFFER'}"]
    if emit_bench:
        metrics = {"tok_per_forward_ratio": ratio,
                   "acceptance_rate": on["spec_acceptance_rate"]}
        for r in (off, on):
            metrics.update({f"{r['mode']}.{key}": v
                            for key, v in r.items()})
        path = _emit_bench_json(
            {"mode": "speculate", "arch": cfg.name, "target": target.name,
             "n_requests": n_requests, "speculate_tokens": k}, metrics)
        lines.append(f"bench metrics -> {path}")
    if not identical:
        raise SystemExit(
            "serve_bench --speculate: speculative outputs differ from the "
            "non-speculative run — greedy speculation must be bit-exact")
    if require_speculate_win and ratio < 1.5:
        raise SystemExit(
            "serve_bench --require-speculate-win: expected >=1.5x decode "
            f"tokens-per-forward with speculation on; got {ratio:.2f}x "
            f"(acceptance {on['spec_acceptance_rate']:.2f}) — lengthen the "
            "stream's repetition or raise --speculate-tokens")
    rows = [[r["mode"], f"{r['tok_per_forward']:.2f}",
             f"{r['tok_per_s']:.1f}", r["n_tokens"], r["decode_steps"],
             r["host_syncs"],
             f"{r['ttft_steps_p50']:.0f}/{r['ttft_steps_p95']:.0f}",
             f"{r.get('spec_acceptance_rate', 0.0):.2f}",
             f"{r['wall_s']*1e3:.0f} ms"] for r in (off, on)]
    table = fmt_table(
        ["mode", "tok/fwd", "tok/s", "tokens", "forwards", "syncs",
         "ttft p50/95", "accept", "wall"],
        rows, title=f"Speculative decode bench — {cfg.name}, "
                    f"{n_requests} requests, k={k} ({target.name})")
    return "\n".join([table] + lines)


def run_disagg(target_name=None, arch: str = "qwen2.5-3b",
               n_requests: int = 24, prompt_len: int = 16,
               gen_len: int = 16, n_slots: Optional[int] = None,
               seed: int = 0, page_tokens: int = 8,
               layer0_bytes: Optional[int] = None,
               layer1_bytes: Optional[int] = None, max_slots: int = 32,
               long_prompt_len: int = 512, n_long: int = 3,
               long_gen_len: int = 8, chunk_prefill_tokens: int = 0,
               sync_interval: int = 8, disagg_win_min: float = 1.15,
               flat_p99_tol: float = 0.10,
               require_disagg_win: bool = False,
               emit_bench: bool = False) -> str:
    """Disaggregated-roles head-to-head (see module doc): mixed
    long-prompt + decode traffic through the SAME paged pool geometry,
    combined engine vs prefill/decode role split.

    Both runs are phase-timed (every phase blocks on its device work), so
    the decode clock is measured, not simulated: combined, each boundary's
    prefill chunks execute inside the decode engine's dispatch stream and
    the full boundary wall is the inter-token gap every decode consumer
    observes; disaggregated, the prompt chunks run on the prefill role and
    the decode consumer's clock spans only the decode dispatch + the
    decode role's drain fetch (``boundary_decode_wall_s``).
    """
    import jax
    import numpy as np
    from repro.configs import get_reduced
    from repro.core.target import get_target
    from repro.models import build_model
    from repro.serve.engine import Engine, EngineConfig
    from repro.serve.scheduler import (Scheduler, derive_page_geometry,
                                       derive_prefill_chunk,
                                       kv_bytes_per_token, percentile,
                                       synthetic_stream)

    with target_scope(target_name):
        target = get_target()
        cfg = get_reduced(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        shorts = synthetic_stream(n_requests, prompt_len, gen_len,
                                  cfg.vocab_size, seed)
        rng = np.random.RandomState(seed + 1)
        longs = [{"prompt": rng.randint(2, cfg.vocab_size,
                                        size=long_prompt_len
                                        ).astype(np.int32),
                  "max_new_tokens": long_gen_len}
                 for _ in range(max(1, n_long))]
        # interleave: every batch of short requests is followed by a long
        # prompt, so prompt chunks keep landing while the pool decodes
        stream = []
        per = max(1, len(shorts) // (len(longs) + 1))
        li = 0
        for i, spec in enumerate(shorts):
            stream.append(spec)
            if (i + 1) % per == 0 and li < len(longs):
                stream.append(longs[li])
                li += 1
        stream.extend(longs[li:])
        chunk = chunk_prefill_tokens or derive_prefill_chunk(cfg)
        max_len = long_prompt_len + max(gen_len, long_gen_len)
        n_slots = n_slots or 8
        if layer0_bytes is None:
            resident = (n_slots * (prompt_len + gen_len + page_tokens)
                        + n_long * (long_prompt_len + long_gen_len
                                    + page_tokens))
            layer0_bytes = kv_bytes_per_token(cfg) * resident
        geom = derive_page_geometry(cfg, max_len, page_tokens=page_tokens,
                                    max_slots=max_slots,
                                    layer0_bytes=layer0_bytes,
                                    layer1_bytes=layer1_bytes)

        def one(disagg: bool) -> Dict:
            # a fresh engine per mode keeps phase/role accounting separate;
            # phase_timing on BOTH sides so each phase blocks identically
            engine = Engine(model, params,
                            EngineConfig(max_len=max_len,
                                         sync_interval=sync_interval,
                                         phase_timing=True,
                                         disaggregate=disagg))

            def serve_once():
                sch = Scheduler(n_slots=n_slots, pages=geom,
                                chunk_prefill_tokens=chunk,
                                disaggregate=disagg)
                for spec in stream:
                    sch.submit(spec["prompt"], spec["max_new_tokens"])
                t0 = time.monotonic()
                rep = engine.serve(scheduler=sch)
                return rep, time.monotonic() - t0

            serve_once()                      # warmup: compile
            rep, dt = serve_once()
            st = rep.stats
            # the decode consumer's clock: full boundary combined, the
            # decode role's own span disaggregated
            walls = (st["boundary_decode_wall_s"] if disagg
                     else st["boundary_wall_s"])
            decode_wall = sum(walls)
            samples: List[float] = []
            for w, t in zip(walls, st["boundary_tokens"]):
                samples.extend([w / sync_interval] * t)
            n_tokens = sum(len(r.tokens) for r in rep.requests)
            decode_tokens = st.get(
                "decode_tokens",
                n_tokens - sum(1 for r in rep.requests if r.tokens))
            rec = {
                "mode": "disaggregated" if disagg else "combined",
                "wall_s": dt,
                "n_tokens": n_tokens,
                "decode_tokens": decode_tokens,
                "decode_wall_s": decode_wall,
                # the gated metric: decode tokens over the decode clock
                "decode_tok_per_s": (decode_tokens / decode_wall
                                     if decode_wall else 0.0),
                "intertoken_p50_ms": percentile(samples, 50) * 1e3,
                "intertoken_p99_ms": percentile(samples, 99) * 1e3,
                "tok_per_s": n_tokens / dt if dt else 0.0,
                "boundaries": len(st["boundary_wall_s"]),
                "decode_steps": st["decode_steps"],
                "host_syncs": st["host_syncs"],
                "completed": st["drained"],
                "n_slots": n_slots,
                "pool_bytes": st["pool_bytes"],
                "pages_high_water": st["pages_high_water"],
                "preemptions": st["preemptions"],
                "prefill_chunks": st["prefill_chunks"],
                "handovers": st["handovers"],
                "handover_pages": st["handover_pages"],
                "phase_s": dict(st.get("phase_s", {})),
                "outputs": {r.rid: list(r.tokens) for r in rep.requests},
            }
            if disagg:
                rec["host_syncs_by_role"] = dict(st["host_syncs_by_role"])
                rec["role_s"] = dict(st.get("role_s", {}))
                for role, n in rec["host_syncs_by_role"].items():
                    if n > rec["boundaries"]:
                        raise SystemExit(
                            f"serve_bench --disaggregate: {role} role made "
                            f"{n} host syncs over {rec['boundaries']} "
                            "boundaries — at most one per role per boundary")
            return rec

        comb = one(False)
        dis = one(True)

    outputs = (comb.pop("outputs"), dis.pop("outputs"))
    identical = outputs[0] == outputs[1]
    if not identical:
        raise SystemExit(
            "serve_bench --disaggregate: disaggregated outputs differ from "
            "the combined engine — the role split must be bit-exact")
    ratio = (dis["decode_tok_per_s"] / comb["decode_tok_per_s"]
             if comb["decode_tok_per_s"] else 0.0)
    p99_ratio = (dis["intertoken_p99_ms"]
                 / max(comb["intertoken_p99_ms"], 1e-9))
    artifact = {
        "arch": cfg.name, "target": target.name,
        "n_requests": len(stream), "long_prompt_len": long_prompt_len,
        "n_long": n_long, "chunk_prefill_tokens": chunk,
        "sync_interval": sync_interval, "layer0_bytes": layer0_bytes,
        "decode_tok_per_s_ratio": ratio, "p99_ratio": p99_ratio,
        "disagg_win_min": disagg_win_min, "flat_p99_tol": flat_p99_tol,
        "outputs_bit_identical": True,
        "combined": comb, "disaggregated": dis,
    }
    save_artifact("serve_disagg_bench.json", artifact)
    lines = [
        f"disaggregated roles ({dis['handovers']} handovers, "
        f"{dis['handover_pages']} pages moved zero-copy, same "
        f"{dis['pool_bytes']} layer-0 bytes): decode "
        f"{dis['decode_tok_per_s']:.1f} vs {comb['decode_tok_per_s']:.1f} "
        f"tok/s (x{ratio:.2f}), inter-token p99 "
        f"{dis['intertoken_p99_ms']:.2f} vs "
        f"{comb['intertoken_p99_ms']:.2f} ms (x{p99_ratio:.2f}, tol "
        f"{flat_p99_tol:.0%}), role syncs "
        f"{dis['host_syncs_by_role']}, outputs bit-identical"]
    if emit_bench:
        metrics = {"decode_tok_per_s_ratio": ratio,
                   "p99_ratio": p99_ratio}
        for r in (comb, dis):
            metrics.update({f"{r['mode']}.{k}": v for k, v in r.items()})
            metrics.update({f"{r['mode']}.phase_{k}_s": v
                            for k, v in r["phase_s"].items()})
        path = _emit_bench_json(
            {"mode": "disaggregate", "arch": cfg.name,
             "target": target.name, "n_requests": len(stream),
             "long_prompt_len": long_prompt_len,
             "chunk_prefill_tokens": chunk,
             "sync_interval": sync_interval}, metrics)
        lines.append(f"bench metrics -> {path}")
    if require_disagg_win:
        if ratio < disagg_win_min:
            raise SystemExit(
                "serve_bench --require-disagg-win: expected >="
                f"{disagg_win_min:.2f}x decode tok/s from the role split; "
                f"got x{ratio:.2f} — the stream's prompt work is too thin "
                "to matter (lengthen --long-prompt-len or add --n-long)")
        if p99_ratio > 1 + flat_p99_tol:
            raise SystemExit(
                "serve_bench --require-disagg-win: disaggregated p99 "
                f"inter-token moved x{p99_ratio:.2f} vs combined "
                f"(tolerance {flat_p99_tol:.0%}) — the decode role is not "
                "isolated from prompt work")
    phase_keys = ("prefill", "insert", "generate", "drain", "handover")
    rows = [[r["mode"], f"{r['decode_tok_per_s']:.1f}",
             f"{r['intertoken_p50_ms']:.2f}/{r['intertoken_p99_ms']:.2f}",
             r["n_tokens"], r["decode_tokens"], r["handovers"],
             r["prefill_chunks"], r["preemptions"],
             f"{r['host_syncs']}/{r['boundaries']}",
             f"{r['wall_s']*1e3:.0f} ms"] for r in (comb, dis)]
    table = fmt_table(
        ["mode", "dec tok/s", "it p50/p99 ms", "tokens", "dec toks",
         "handover", "chunks", "preempt", "syncs/bnd", "wall"],
        rows, title=f"Disaggregated serve bench — {cfg.name}, "
                    f"{len(stream)} requests ({n_long} x "
                    f"{long_prompt_len}-token prompts), chunk={chunk} "
                    f"({target.name})")
    phase_rows = [[r["mode"]] + [f"{r['phase_s'].get(k, 0.0)*1e3:.0f}"
                                 for k in phase_keys]
                  for r in (comb, dis)]
    phase_table = fmt_table(
        ["mode"] + [f"{k} ms" for k in phase_keys], phase_rows,
        title="Phase breakdown (both runs phase-timed)")
    return "\n".join([table, phase_table] + lines)


def run_mesh(target_name=None, arch: str = "qwen2.5-3b",
             n_requests: int = 32, prompt_len: int = 16,
             gen_len: int = 12, seed: int = 0, page_tokens: int = 8,
             layer0_bytes: Optional[int] = None,
             layer1_bytes: Optional[int] = None, max_slots: int = 32,
             mesh_spec: str = "2", mesh_axes: str = "data,model",
             sync_interval: Optional[int] = None,
             require_scaling: bool = False,
             emit_bench: bool = False) -> str:
    """Mesh-sharded serving head-to-head: the same paged stream served
    single-device and under the ``--mesh`` mesh, same per-shard layer-0
    byte budget (the mesh exposes ``kv_shards`` x the aggregate pool).

    The gated metric is **modeled decode scaling**: tokens per decode
    forward over the per-shard resident-KV bytes that forward sweeps.
    Head-axis page placement keeps per-shard sweep bytes flat while the
    scaled budget admits ``kv_shards`` x the slots, so tokens per sweep
    — decode throughput on the modeled memory-bound target — scales with
    the mesh. Host wall tok/s is reported but NOT gated: every forced
    host-platform device re-runs the full FLOPs, so wall time cannot
    show the memory-side win. Sync discipline is asserted, not gated:
    one host sync per drain boundary on both sides.

    Bit-exactness is asserted per mesh size, against the SAME engine's
    one-shot rollout: tensor-parallel row-sharded matmuls reassociate
    the contraction sum across shards (the all-reduce adds partials the
    single device accumulated inside one dot), so a near-tie greedy
    argmax may legitimately flip ACROSS mesh sizes — but within one mesh
    size, continuous batching, paging and head-axis placement must not
    move a single bit.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_reduced
    from repro.core.target import get_target
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_cli_mesh
    from repro.models import build_model
    from repro.serve.engine import Engine, EngineConfig
    from repro.serve.scheduler import (Scheduler, derive_n_slots,
                                       derive_page_geometry,
                                       kv_bytes_per_token, kv_shards,
                                       percentile, synthetic_stream)

    with target_scope(target_name):
        target = get_target()
        cfg = get_reduced(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        stream = synthetic_stream(n_requests, prompt_len, gen_len,
                                  cfg.vocab_size, seed)
        max_len = prompt_len + gen_len
        base_slots = derive_n_slots(cfg, max_len, max_slots=8)
        l0 = (layer0_bytes if layer0_bytes is not None
              else base_slots * kv_bytes_per_token(cfg) * max_len)
        mesh = make_cli_mesh(mesh_spec, mesh_axes)
        model_shards = shd.axis_size(mesh, shd.MODEL_AXIS)
        data_shards = shd.axis_size(mesh, shd.DATA_AXIS)
        shards = kv_shards(cfg, model_shards) * max(1, data_shards)

        def one(meshed: bool) -> Dict:
            ms = model_shards if meshed else 1
            ds = data_shards if meshed else 1
            ks = shards if meshed else 1
            geom = derive_page_geometry(
                cfg, max_len, page_tokens=page_tokens,
                max_slots=max_slots, layer0_bytes=l0,
                layer1_bytes=layer1_bytes, model_shards=ms)
            slots = derive_n_slots(cfg, max_len, pages=geom,
                                   max_slots=max_slots, model_shards=ms,
                                   data_shards=ds)
            engine = Engine(model, params,
                            EngineConfig(max_len=max_len,
                                         sync_interval=sync_interval or 4,
                                         mesh=mesh if meshed else None))
            # this engine's own ground truth: one-shot greedy rollouts
            refs = []
            for spec in stream:
                toks, _ = engine.generate(
                    {"tokens": jnp.asarray(spec["prompt"])[None]},
                    n_steps=spec["max_new_tokens"])
                refs.append([int(t) for t in np.asarray(toks)[0]])

            def serve_once():
                sch = Scheduler(n_slots=slots, pages=geom)
                rids = [sch.submit(s["prompt"], s["max_new_tokens"]).rid
                        for s in stream]
                t0 = time.monotonic()
                rep = engine.serve(scheduler=sch)
                return rids, rep, time.monotonic() - t0

            serve_once()                      # warmup: compile
            rids, rep, dt = serve_once()
            for rid, ref in zip(rids, refs):
                got = rep.outputs[rid]
                if not got or got != ref[:len(got)]:
                    raise SystemExit(
                        f"serve_bench --mesh: {'mesh' if meshed else 'base'}"
                        " continuous outputs are not a prefix of the same "
                        "engine's one-shot rollout — sharded serving must "
                        "be bit-exact against its own reference")
            st = rep.stats
            n_tokens = sum(len(r.tokens) for r in rep.requests)
            page_bytes = st["pool_bytes"] // max(st["n_pages"], 1)
            return {
                "mode": f"mesh={ms * ds}" if meshed else "mesh=1",
                "wall_s": dt,
                "n_tokens": n_tokens,
                "tok_per_s": n_tokens / dt if dt else 0.0,
                "decode_steps": st["decode_steps"],
                "host_syncs": st["host_syncs"],
                "boundaries": len(st["boundary_wall_s"]),
                "completed": st["drained"],
                "n_slots": slots,
                "kv_shards": ks,
                "pool_bytes": st["pool_bytes"],
                "per_shard_pool_bytes": st["pool_bytes"] // ks,
                "n_pages": st["n_pages"],
                "pages_high_water": st["pages_high_water"],
                "per_shard_pages_high_water":
                    -(-st["pages_high_water"] // ks),
                # per-shard resident-KV bytes one decode forward sweeps:
                # the forward's modeled cost on the memory-bound target
                "per_shard_sweep_bytes":
                    st["pages_high_water"] * page_bytes // ks,
                "tok_per_forward": (n_tokens / st["decode_steps"]
                                    if st["decode_steps"] else 0.0),
                "ttft_emit_p50": percentile(st["ttft_emit_steps"], 50),
                "ttft_emit_p95": percentile(st["ttft_emit_steps"], 95),
            }

        base = one(False)
        on_mesh = one(True)

    for rec in (base, on_mesh):
        if rec["host_syncs"] != rec["boundaries"]:
            raise SystemExit(
                f"serve_bench --mesh: {rec['mode']} made "
                f"{rec['host_syncs']} host syncs over {rec['boundaries']} "
                "drain boundaries — sharding must not add sync points")

    def modeled(rec):
        return rec["tok_per_forward"] / max(rec["per_shard_sweep_bytes"], 1)

    scaling = modeled(on_mesh) / modeled(base) if modeled(base) else 0.0
    wall_scaling = (on_mesh["tok_per_s"] / base["tok_per_s"]
                    if base["tok_per_s"] else 0.0)
    artifact = {
        "arch": cfg.name, "target": target.name, "n_requests": n_requests,
        "prompt_len": prompt_len, "gen_len": gen_len,
        "mesh": mesh_spec, "mesh_axes": mesh_axes,
        "model_shards": model_shards, "data_shards": data_shards,
        "layer0_bytes": l0,
        "scaling_modeled": scaling, "scaling_wall": wall_scaling,
        "outputs_prefix_of_one_shot": True,
        "base": base, "mesh_run": on_mesh,
    }
    save_artifact("serve_mesh_bench.json", artifact)
    lines = [
        f"mesh scaling ({data_shards}x{model_shards} data x model, "
        f"{on_mesh['kv_shards']}x kv pool, per-shard "
        f"{on_mesh['per_shard_pool_bytes']} layer-0 bytes): modeled decode "
        f"x{scaling:.2f} ({on_mesh['tok_per_forward']:.2f} vs "
        f"{base['tok_per_forward']:.2f} tok/fwd at flat per-shard sweep), "
        f"wall x{wall_scaling:.2f} (not gated: host devices re-run full "
        f"FLOPs), syncs/boundary {on_mesh['host_syncs']}/"
        f"{on_mesh['boundaries']} vs {base['host_syncs']}/"
        f"{base['boundaries']}, outputs one-shot-exact"]
    if emit_bench:
        metrics = {"scaling_modeled": scaling,
                   "scaling_wall": wall_scaling}
        for key, rec in (("base", base), ("mesh", on_mesh)):
            metrics.update({f"{key}.{k}": v for k, v in rec.items()})
        path = _emit_bench_json(
            {"mode": "mesh", "arch": cfg.name, "target": target.name,
             "n_requests": n_requests, "mesh": mesh_spec,
             "mesh_axes": mesh_axes}, metrics)
        lines.append(f"bench metrics -> {path}")
    if require_scaling and scaling < 1.7:
        raise SystemExit(
            "serve_bench --require-scaling: expected >=1.7x modeled decode "
            f"scaling at mesh {mesh_spec}; got x{scaling:.2f} — the pool "
            "budget did not scale (check kv_shards: MLA-latent and SSM "
            "caches replicate) or slots were capped by --max-slots")
    rows = [[r["mode"], f"{r['tok_per_forward']:.2f}",
             f"{r['tok_per_s']:.1f}", r["n_tokens"], r["n_slots"],
             r["kv_shards"], r["per_shard_pool_bytes"],
             r["pages_high_water"], r["per_shard_pages_high_water"],
             f"{r['host_syncs']}/{r['boundaries']}",
             f"{r['ttft_emit_p50']:.0f}/{r['ttft_emit_p95']:.0f}",
             f"{r['wall_s']*1e3:.0f} ms"] for r in (base, on_mesh)]
    table = fmt_table(
        ["mode", "tok/fwd", "tok/s", "tokens", "slots", "kv shards",
         "shard bytes", "pages hw", "shard hw", "syncs/bnd",
         "ttft emit 50/95", "wall"],
        rows, title=f"Mesh-sharded serve bench — {cfg.name}, "
                    f"{n_requests} requests, mesh {mesh_spec} "
                    f"({target.name})")
    return "\n".join([table] + lines)


def run_quant(target_name=None, arch: str = "qwen2.5-3b",
              n_requests: int = 32, prompt_len: int = 16,
              gen_len: int = 12, seed: int = 0, *, page_tokens: int = 8,
              layer0_bytes: Optional[int] = None,
              layer1_bytes: Optional[int] = None, max_slots: int = 32,
              kv_quant: str = "int8", park_idle: int = 0,
              sync_interval: Optional[int] = None,
              residency_win_min: float = 1.8,
              require_residency_win: bool = False,
              emit_bench: bool = False) -> str:
    """Tier-codec head-to-head: fp16 vs quantized pages, SAME layer-0
    bytes. The quantized page is smaller, so the same budget holds more
    pages and the pool keeps more sessions concurrently resident — the
    capacity win, gated as residents-per-byte. Greedy first-token
    agreement against the fp16 run bounds the quantization cost."""
    import jax
    from repro.configs import get_reduced
    from repro.core.target import get_target
    from repro.models import build_model
    from repro.serve.engine import Engine, EngineConfig
    from repro.serve.scheduler import (DECODING, PREFILLING, Scheduler,
                                       derive_n_slots, derive_page_geometry,
                                       kv_bytes_per_token, percentile,
                                       synthetic_stream)

    with target_scope(target_name):
        target = get_target()
        cfg = get_reduced(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        stream = synthetic_stream(n_requests, prompt_len, gen_len,
                                  cfg.vocab_size, seed)
        max_len = prompt_len + gen_len
        engine = Engine(model, params,
                        EngineConfig(max_len=max_len,
                                     sync_interval=sync_interval or 4))
        # default budget: four full-depth fp16 residents — tight enough
        # that fp16 concurrency is page-capped, so the codec's smaller
        # page shows up as MORE residents, not just slack
        l0 = (layer0_bytes if layer0_bytes is not None
              else 4 * kv_bytes_per_token(cfg) * max_len)

        def one(qq: str) -> Dict:
            geom = derive_page_geometry(
                cfg, max_len, page_tokens=page_tokens, max_slots=max_slots,
                layer0_bytes=l0, layer1_bytes=layer1_bytes, kv_quant=qq)
            slots = derive_n_slots(cfg, max_len, pages=geom,
                                   max_slots=max_slots)

            def serve_once():
                sch = Scheduler(n_slots=slots, pages=geom)
                rids = [sch.submit(s["prompt"], s["max_new_tokens"]).rid
                        for s in stream]
                rid_map = {r: r for r in rids}
                t0 = time.monotonic()
                if park_idle:
                    engine.serve(scheduler=sch, max_steps=park_idle)
                    blobs = []
                    for slot in sorted(list(sch.active)):
                        req = sch.active[slot]
                        if req.status == DECODING:
                            blobs.append(
                                (req.rid,
                                 engine.park_request(sch, req.rid)))
                        elif req.status == PREFILLING:
                            sch.requeue(slot)
                    for old_rid, blob in blobs:
                        rid_map[old_rid] = \
                            engine.resume_parked(sch, blob).rid
                rep = engine.serve(scheduler=sch)
                return rids, rid_map, rep, time.monotonic() - t0

            serve_once()                          # warmup: compile
            rids, rid_map, rep, dt = serve_once()
            st = rep.stats
            n_tokens = sum(len(r.tokens) for r in rep.requests)
            return {
                "mode": f"kv-quant={qq}",
                "codec": qq,
                "wall_s": dt,
                "n_tokens": n_tokens,
                "tok_per_s": n_tokens / dt if dt else 0.0,
                "completed": st["drained"],
                "n_slots": slots,
                "n_pages": st["n_pages"],
                "pool_bytes": st["pool_bytes"],
                "page_bytes": geom.page_bytes,
                "resident_high_water": st["resident_high_water"],
                "residents_per_mb":
                    st["resident_high_water"] * 2**20 / max(l0, 1),
                "pages_high_water": st["pages_high_water"],
                "preemptions": st["preemptions"],
                "spilled_pages": st["spilled_pages"],
                "parks": st["parks"],
                "park_resumes": st["park_resumes"],
                "ttft_emit_p50": percentile(st["ttft_emit_steps"], 50),
                "ttft_emit_p95": percentile(st["ttft_emit_steps"], 95),
                "outputs": [rep.outputs[rid_map[r]] for r in rids],
            }

        base = one("fp16")
        quant = one(kv_quant)

    for rec in (base, quant):
        if rec["completed"] != n_requests:
            raise SystemExit(
                f"serve_bench --kv-quant: {rec['mode']} drained "
                f"{rec['completed']}/{n_requests} requests")
    outs_base = base.pop("outputs")
    outs_quant = quant.pop("outputs")
    if any(not o for o in outs_base) or any(not o for o in outs_quant):
        raise SystemExit(
            "serve_bench --kv-quant: a drained request emitted no tokens")
    agreement = sum(a[0] == b[0]
                    for a, b in zip(outs_base, outs_quant)) / n_requests
    ratio = (quant["resident_high_water"]
             / max(base["resident_high_water"], 1))
    pages_ratio = (quant["n_pages"] - 1) / max(base["n_pages"] - 1, 1)
    artifact = {
        "arch": cfg.name, "target": target.name, "n_requests": n_requests,
        "prompt_len": prompt_len, "gen_len": gen_len,
        "kv_quant": kv_quant, "layer0_bytes": l0, "park_idle": park_idle,
        "residency_ratio": ratio, "pages_ratio": pages_ratio,
        "first_token_agreement": agreement,
        "base": base, "quant": quant,
    }
    save_artifact("serve_quant_bench.json", artifact)
    lines = [
        f"tier codecs ({kv_quant} vs fp16, same {l0} layer-0 bytes): "
        f"residency {quant['resident_high_water']} vs "
        f"{base['resident_high_water']} concurrent residents "
        f"({ratio:.2f}x), {quant['n_pages'] - 1} vs {base['n_pages'] - 1} "
        f"data pages ({pages_ratio:.2f}x), greedy first-token agreement "
        f"{agreement:.2f}"]
    if park_idle:
        lines.append(
            f"host parking: {quant['parks']} parked at step {park_idle}, "
            f"{quant['park_resumes']} resumed, stream completed")
    if emit_bench:
        metrics = {"residency_ratio": ratio, "pages_ratio": pages_ratio,
                   "first_token_agreement": agreement}
        for key, rec in (("base", base), ("quant", quant)):
            metrics.update({f"{key}.{k}": v for k, v in rec.items()})
        path = _emit_bench_json(
            {"mode": "kv-quant", "arch": cfg.name, "target": target.name,
             "n_requests": n_requests, "kv_quant": kv_quant,
             "layer0_bytes": l0, "park_idle": park_idle}, metrics)
        lines.append(f"bench metrics -> {path}")
    if require_residency_win and (ratio < residency_win_min
                                  or agreement < 0.75):
        raise SystemExit(
            "serve_bench --require-residency-win: expected >="
            f"{residency_win_min}x concurrent residents at >=0.75 "
            f"first-token agreement; got x{ratio:.2f} at {agreement:.2f} "
            "— either the budget is slack (fp16 was not page-capped) or "
            "the codec drifted")
    rows = [[r["mode"], r["n_slots"], r["resident_high_water"],
             r["n_pages"] - 1, r["page_bytes"], r["pool_bytes"],
             r["preemptions"], r["parks"],
             f"{r['ttft_emit_p50']:.0f}/{r['ttft_emit_p95']:.0f}",
             f"{r['tok_per_s']:.1f}"] for r in (base, quant)]
    table = fmt_table(
        ["mode", "slots", "res hw", "pages", "page B", "pool B",
         "preempt", "parks", "ttft 50/95", "tok/s"],
        rows, title=f"Tier-codec serve bench — {cfg.name}, "
                    f"{n_requests} requests, {l0} layer-0 bytes "
                    f"({target.name})")
    return "\n".join([table] + lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=12)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="also run the paged two-tier pool inside the dense "
                         "pool's layer-0 byte budget")
    ap.add_argument("--page-tokens", type=int, default=8,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--layer0-bytes", type=int, default=None,
                    help="layer-0 (hot tier) budget; default: the dense "
                         "pool's footprint")
    ap.add_argument("--layer1-bytes", type=int, default=None,
                    help="layer-1 (spill tier) budget; default: derived "
                         "from the target's TieredPartition")
    ap.add_argument("--max-slots", type=int, default=32,
                    help="cap on paged-mode concurrent slots")
    ap.add_argument("--require-spill", action="store_true",
                    help="fail unless the layer-1 spill tier was exercised")
    ap.add_argument("--prefix-share", action="store_true",
                    help="replay a shared-system-prompt stream through the "
                         "paged pool with prefix sharing off vs on (same "
                         "layer-0 bytes; outputs must be bit-identical)")
    ap.add_argument("--system-len", type=int, default=None,
                    help="shared system-prompt length for --prefix-share "
                         "(default: 3 full pages)")
    ap.add_argument("--require-share-win", action="store_true",
                    help="fail unless sharing shows >=1.5x mapped/physical "
                         "residency and no-worse TTFT p95")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="run the chunked-prefill admission-stall "
                         "head-to-head instead of the mode comparison: "
                         "baseline stream vs one-shot vs chunked admission "
                         "of one --long-prompt-len prompt")
    ap.add_argument("--long-prompt-len", type=int, default=4096,
                    help="length of the admission-stall prompt "
                         "(--chunked-prefill)")
    ap.add_argument("--chunk-prefill-tokens", type=int, default=0,
                    metavar="N",
                    help="per-boundary prefill-token budget for the "
                         "chunked run (0: derive from the target's "
                         "CapacityPartition)")
    ap.add_argument("--sync-interval", type=int, default=None,
                    help="decode steps per drain boundary (default: 4, or "
                         "8 in --chunked-prefill mode)")
    ap.add_argument("--require-flat-p99", action="store_true",
                    help="fail unless chunked p99 inter-token latency "
                         "stays within --flat-p99-tol of baseline while "
                         "the one-shot admission degrades past it")
    ap.add_argument("--flat-p99-tol", type=float, default=0.10,
                    help="relative p99 tolerance for --require-flat-p99")
    ap.add_argument("--repeats", type=int, default=3,
                    help="measured passes per run in --chunked-prefill "
                         "mode; the median-p99 pass is reported")
    ap.add_argument("--speculate", action="store_true",
                    help="run the speculative-decoding head-to-head "
                         "instead of the mode comparison: the repetitive "
                         "stream through the paged pool, speculation off "
                         "vs on in the same layer-0 bytes")
    ap.add_argument("--speculate-tokens", type=int, default=0, metavar="K",
                    help="draft tokens per slot per boundary for "
                         "--speculate (0: derive from the target's "
                         "CapacityPartition)")
    ap.add_argument("--require-speculate-win", action="store_true",
                    help="fail unless speculation shows >=1.5x decode "
                         "tokens-per-forward with bit-identical outputs")
    ap.add_argument("--mesh", default="1",
                    help="device mesh sizes, 'DxM' (matching --mesh-axes) "
                         "or one int (model-parallel shorthand: '2' = "
                         "1x2); any size > 1 runs the mesh-sharded "
                         "head-to-head instead of the mode comparison")
    ap.add_argument("--mesh-axes", default="data,model",
                    help="comma-separated axis names for --mesh")
    ap.add_argument("--require-scaling", action="store_true",
                    help="fail unless the --mesh run shows >=1.7x modeled "
                         "decode scaling with one-shot-exact outputs and "
                         "one host sync per drain boundary")
    ap.add_argument("--disaggregate", action="store_true",
                    help="run the prefill/decode role-split head-to-head "
                         "instead of the mode comparison: mixed long-"
                         "prompt + decode traffic through the same paged "
                         "pool, combined engine vs disaggregated roles")
    ap.add_argument("--n-long", type=int, default=3,
                    help="long prompts interleaved into the --disaggregate "
                         "stream")
    ap.add_argument("--require-disagg-win", action="store_true",
                    help="fail unless the role split shows >= "
                         "--disagg-win-min x decode tok/s at inter-token "
                         "p99 within --flat-p99-tol of combined, with "
                         "bit-identical outputs")
    ap.add_argument("--disagg-win-min", type=float, default=1.15,
                    help="decode tok/s ratio --require-disagg-win gates on")
    ap.add_argument("--kv-quant", choices=("fp16", "fp8", "int8"),
                    default=None,
                    help="run the tier-codec head-to-head instead of the "
                         "mode comparison: the same stream in the same "
                         "layer-0 bytes, fp16 pages vs this codec")
    ap.add_argument("--park-idle", type=int, default=0, metavar="N",
                    help="inside the --kv-quant runs: after N decode "
                         "steps park every decoding resident to the "
                         "layer-2 host tier, resume, and finish")
    ap.add_argument("--require-residency-win", action="store_true",
                    help="fail unless the quantized run holds >=1.8x the "
                         "fp16 run's concurrent residents in the same "
                         "layer-0 bytes at >=0.75 greedy first-token "
                         "agreement")
    ap.add_argument("--emit-bench", action="store_true",
                    help="write the flat cross-PR metric file "
                         "BENCH_%d.json at the repo root" % BENCH_ID)
    add_target_arg(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    try:
        mesh_n = 1
        for part in args.mesh.split("x"):
            mesh_n *= int(part)
    except ValueError:
        mesh_n = 0      # malformed: let parse_mesh raise the real error
    if mesh_n != 1 or args.require_scaling:
        print(run_mesh(
            args.target, args.arch, args.requests,
            args.prompt_len, args.gen_len, args.seed,
            page_tokens=args.page_tokens, layer0_bytes=args.layer0_bytes,
            layer1_bytes=args.layer1_bytes, max_slots=args.max_slots,
            mesh_spec=args.mesh, mesh_axes=args.mesh_axes,
            sync_interval=args.sync_interval,
            require_scaling=args.require_scaling,
            emit_bench=args.emit_bench))
        return 0
    if args.disaggregate or args.require_disagg_win:
        print(run_disagg(
            args.target, args.arch, args.requests, args.prompt_len,
            args.gen_len, args.slots, args.seed,
            page_tokens=args.page_tokens, layer0_bytes=args.layer0_bytes,
            layer1_bytes=args.layer1_bytes, max_slots=args.max_slots,
            long_prompt_len=args.long_prompt_len, n_long=args.n_long,
            chunk_prefill_tokens=args.chunk_prefill_tokens,
            sync_interval=args.sync_interval or 8,
            disagg_win_min=args.disagg_win_min,
            flat_p99_tol=args.flat_p99_tol,
            require_disagg_win=args.require_disagg_win,
            emit_bench=args.emit_bench))
        return 0
    if args.kv_quant or args.require_residency_win:
        print(run_quant(
            args.target, args.arch, args.requests, args.prompt_len,
            args.gen_len, args.seed, page_tokens=args.page_tokens,
            layer0_bytes=args.layer0_bytes,
            layer1_bytes=args.layer1_bytes, max_slots=args.max_slots,
            kv_quant=args.kv_quant or "int8", park_idle=args.park_idle,
            sync_interval=args.sync_interval,
            require_residency_win=args.require_residency_win,
            emit_bench=args.emit_bench))
        return 0
    if args.speculate:
        print(run_speculate(
            args.target, args.arch, args.requests,
            args.prompt_len, args.gen_len,
            args.slots, args.seed, page_tokens=args.page_tokens,
            layer0_bytes=args.layer0_bytes,
            layer1_bytes=args.layer1_bytes, max_slots=args.max_slots,
            speculate_tokens=args.speculate_tokens,
            sync_interval=args.sync_interval,
            require_speculate_win=args.require_speculate_win,
            emit_bench=args.emit_bench))
        return 0
    if args.chunked_prefill:
        print(run_chunked(
            args.target, args.arch, args.requests, args.prompt_len,
            args.gen_len, args.slots or 16, args.seed,
            page_tokens=args.page_tokens, layer0_bytes=args.layer0_bytes,
            layer1_bytes=args.layer1_bytes, max_slots=args.max_slots,
            prefix_share=args.prefix_share, system_len=args.system_len,
            long_prompt_len=args.long_prompt_len,
            chunk_prefill_tokens=args.chunk_prefill_tokens,
            sync_interval=args.sync_interval or 32,
            flat_p99_tol=args.flat_p99_tol,
            require_flat_p99=args.require_flat_p99,
            require_spill=args.require_spill, repeats=args.repeats,
            emit_bench=args.emit_bench))
        return 0
    print(run(args.target, args.arch, args.requests, args.prompt_len,
              args.gen_len, args.slots, args.seed, paged=args.paged,
              page_tokens=args.page_tokens, layer0_bytes=args.layer0_bytes,
              layer1_bytes=args.layer1_bytes, max_slots=args.max_slots,
              require_spill=args.require_spill,
              prefix_share=args.prefix_share, system_len=args.system_len,
              require_share_win=args.require_share_win,
              sync_interval=args.sync_interval,
              emit_bench=args.emit_bench))
    return 0


if __name__ == "__main__":
    sys.exit(main())
