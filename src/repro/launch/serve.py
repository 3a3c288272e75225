"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Two modes through the same Engine (pooled KV cache):

  * default — one-shot batched greedy decode (prefill + fixed batch),
    reporting total latency and throughput.
  * ``--stream N`` — continuous batching: N synthetic requests with mixed
    prompt/output lengths flow through the scheduler's slot table; reports
    per-request TTFT / end-to-end latency percentiles (from the
    scheduler's per-request clocks) and aggregate tokens/s.
  * ``--stream N --paged`` — the same stream over the paged two-tier pool:
    admission by pages, preempt-and-spill to the layer-1 tier when layer 0
    runs out. ``--page-tokens`` / ``--layer0-bytes`` / ``--layer1-bytes``
    shape the pool; preemption/spill counters join the report.
  * ``--stream N --paged --prefix-share`` — the stream becomes the
    shared-system-prompt workload (every prompt = one common
    ``--system-len`` prefix + a unique tail) and admissions serve the
    shared prefix from ref-counted resident pages, prefilling only the
    tail; prefix hit/miss, shared-token, COW, and mapped-vs-physical page
    counters join the report (DESIGN.md §Prefix sharing & copy-on-write).
  * ``--chunk-prefill-tokens N`` (any stream mode) — chunked prefill:
    admission prefill is capped at N tokens per drain boundary and
    interleaved with decode, so a long prompt no longer stalls in-flight
    requests; ``0`` derives the budget from the target
    (``derive_prefill_chunk``). Chunk counters (chunks, max boundary
    prefill tokens) join the report (DESIGN.md §Chunked prefill).
  * ``--stream N --paged --disaggregate`` — disaggregated prefill/decode
    engine roles over the same paged pool (DESIGN.md §Disaggregated
    serving): admissions and prompt chunks run on the prefill role, the
    batched decode on the decode role, and at each request's final prefill
    chunk its pages hand over by a zero-copy block-table-row move.
    Handover and per-role host-sync counters join the report; outputs are
    bit-identical to the combined engine.
  * ``--speculate-tokens K`` (any stream mode) — self-drafting speculative
    decoding: each drain boundary proposes up to K draft tokens per live
    slot by prompt lookup and scores them all in ONE batched verify
    forward, emitting the accepted prefix + 1 (greedy bit-exact); ``0``
    derives K from the target (``derive_speculate_tokens``). Without
    ``--prefix-share`` the stream becomes the repetitive (motif-tiled)
    workload the proposer is built for; proposed/accepted/rejected and
    acceptance-rate counters join the report (DESIGN.md §Speculative
    decoding).

Hardware target selection: ``--target <name>`` (or ``REPRO_TARGET``) — the
slot/page budgets are derived from that target's CapacityPartition
(two-tier via its stacked TieredPartition in paged mode). On a TPU the
default target is the one registered for the chip's ``device_kind``.

Weights are random (seed 0) and made on the device already in the serving
dtype (:func:`repro.serve.engine.init_serving_params`). Without
``--layer0-bytes``/``--layer1-bytes`` the paged pool gets what the device
has left beside them (:func:`repro.serve.pool.pool_bytes_beside`). The
first line names the device the run is on.
"""

from __future__ import annotations

import argparse
import contextlib
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.core.target import available_targets, use_target
from repro.distributed import sharding as shd
from repro.launch.device import (describe_devices, device_target,
                                 enable_compile_cache)
from repro.launch.mesh import make_cli_mesh
from repro.models import build_model
from repro.serve.engine import Engine, EngineConfig, init_serving_params
from repro.serve.pool import pool_bytes_beside
from repro.serve.scheduler import (DECODING, DRAINED, PREFILLING, Scheduler,
                                   derive_n_slots, derive_page_geometry,
                                   derive_prefill_chunk,
                                   derive_speculate_tokens, kv_shards,
                                   percentile, repetitive_stream,
                                   shared_prefix_stream, synthetic_stream)


def run_stream(engine: Engine, scheduler: Scheduler, stream: list, *,
               park_idle: int = 0) -> dict:
    """Drive a prepared request stream; return counters.

    With ``park_idle`` the stream runs in two phases: serve ``park_idle``
    decode steps, park every decoding resident to the layer-2 host tier
    (mid-prefill residents requeue from scratch — they have nothing to
    resume), resume the parked blobs into the SAME scheduler, and serve to
    completion. Outputs are bit-identical to the uninterrupted run at the
    fp16 codec; the park counters land in the report."""
    n_requests = len(stream)
    for spec in stream:
        scheduler.submit(spec["prompt"], spec["max_new_tokens"])
    t0 = time.monotonic()
    pre_stats = None
    if park_idle:
        engine.serve(scheduler=scheduler, max_steps=park_idle)
        pre_stats = dict(engine.last_stats)
        blobs = []
        for slot in sorted(list(scheduler.active)):
            req = scheduler.active[slot]
            if req.status == DECODING:
                blobs.append(engine.park_request(scheduler, req.rid))
            elif req.status == PREFILLING:
                scheduler.requeue(slot)
        for blob in blobs:
            engine.resume_parked(scheduler, blob)
    report = engine.serve(scheduler=scheduler)
    dt = time.monotonic() - t0
    stats = report.stats
    if pre_stats:
        for k in ("host_syncs", "decode_steps", "chunks"):
            stats[k] = stats.get(k, 0) + pre_stats.get(k, 0)
    n_tokens = sum(len(r.tokens) for r in report.requests)
    served = [r for r in report.requests if r.status == DRAINED]
    decode_steps = [r.finish_step - r.admit_step for r in served
                    if r.finish_step >= 0]
    rec = {
        "n_requests": n_requests,
        "completed": stats["drained"],
        "n_tokens": n_tokens,
        "wall_s": dt,
        "tok_per_s": n_tokens / dt if dt else 0.0,
        "host_syncs": stats["host_syncs"],
        "decode_steps_total": stats["decode_steps"],
        "n_slots": stats["n_slots"],
        "max_slot_reuse": stats["max_slot_reuse"],
        # per-request latency percentiles from the scheduler's clocks —
        # TTFT (submit -> admission) and end-to-end (submit -> drain)
        "ttft_steps_p50": percentile(stats["ttft_steps"], 50),
        "ttft_steps_p95": percentile(stats["ttft_steps"], 95),
        "e2e_steps_p50": percentile(stats["e2e_steps"], 50),
        "e2e_steps_p95": percentile(stats["e2e_steps"], 95),
        "decode_steps_p50": percentile(decode_steps, 50),
        "decode_steps_p95": percentile(decode_steps, 95),
        "preemptions": stats["preemptions"],
        "spilled_pages": stats["spilled_pages"],
        "restores": stats["restores"],
        # chunked prefill: TTFT to the first OUTPUT token (under chunking
        # the final chunk's boundary, later than slot admission) and how
        # much prompt work any single boundary booked
        "ttft_emit_steps_p50": percentile(stats["ttft_emit_steps"], 50),
        "ttft_emit_steps_p95": percentile(stats["ttft_emit_steps"], 95),
        "chunk_prefill_tokens": stats["chunk_prefill_tokens"],
        "prefill_chunks": stats["prefill_chunks"],
        "max_boundary_prefill_tokens": stats["max_boundary_prefill_tokens"],
        "outputs": report.outputs,
    }
    if stats.get("speculate_tokens"):
        rec.update({k: stats[k] for k in (
            "speculate_tokens", "spec_proposed", "spec_accepted",
            "spec_rejected", "spec_acceptance_rate")})
    if stats.get("paged"):
        rec.update({k: stats[k] for k in (
            "page_tokens", "n_pages", "n_spill_pages", "pages_high_water",
            "spill_high_water", "pool_bytes", "spill_bytes",
            "layer0_codec", "layer1_codec", "parks", "park_resumes",
            "resident_high_water")})
    if stats.get("prefix_sharing"):
        rec.update({k: stats[k] for k in (
            "prefix_hits", "prefix_misses", "shared_prefix_tokens",
            "cow_copies", "mapped_high_water")})
    if stats.get("disaggregate"):
        rec.update({
            "disaggregate": True,
            "handovers": stats["handovers"],
            "handover_pages": stats["handover_pages"],
            "host_syncs_by_role": dict(stats["host_syncs_by_role"]),
            "decode_tokens": stats["decode_tokens"],
        })
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2.5-3b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--mesh", default="1",
                    help="device mesh: one int (model-parallel shorthand, "
                         "2 = 1x2) or DxM sizes matching --mesh-axes; "
                         "default 1 = today's single-device path")
    ap.add_argument("--mesh-axes", default="data,model",
                    help="comma-separated axis names the --mesh sizes "
                         "bind to (default data,model)")
    ap.add_argument("--target", default=None, metavar="NAME",
                    help=f"hardware target ({', '.join(available_targets())})")
    ap.add_argument("--stream", type=int, default=0, metavar="N",
                    help="continuous batching over N synthetic requests")
    ap.add_argument("--slots", type=int, default=None,
                    help="override the CapacityPartition-derived slot count")
    ap.add_argument("--sync-interval", type=int, default=8)
    ap.add_argument("--paged", action="store_true",
                    help="serve --stream over the paged two-tier KV pool")
    ap.add_argument("--page-tokens", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--layer0-bytes", type=int, default=None,
                    help="override the layer-0 (hot tier) page-pool budget")
    ap.add_argument("--layer1-bytes", type=int, default=None,
                    help="override the layer-1 (spill tier) budget")
    ap.add_argument("--kv-quant", choices=["fp16", "fp8", "int8"],
                    default=None,
                    help="per-tier KV page codec (paged mode): fp16 is the "
                         "bit-exact identity; fp8/int8 store more pages in "
                         "the same layer-0 bytes at a bounded logit error, "
                         "and the spill tier quantizes at least as hard "
                         "(fp8 spills as int8)")
    ap.add_argument("--park-idle", type=int, default=None, metavar="N",
                    help="after N decode steps, park every decoding "
                         "resident to the layer-2 host tier (zstd-coded "
                         "page bytes + scheduler residue), then resume "
                         "and serve to completion — bit-identical outputs "
                         "at fp16 (paged mode)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="split serving into prefill-role and decode-role "
                         "engines over the shared paged pool; pages hand "
                         "over at the final prefill chunk (requires "
                         "--paged; bit-identical outputs)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="share cached prompt prefixes across requests "
                         "(paged mode; drives a shared-system-prompt stream)")
    ap.add_argument("--system-len", type=int, default=None,
                    help="shared system-prompt length for --prefix-share "
                         "(default: half of --prompt-len)")
    ap.add_argument("--chunk-prefill-tokens", type=int, default=None,
                    metavar="N",
                    help="chunked prefill: cap prompt prefill at N tokens "
                         "per drain boundary, interleaved with decode "
                         "(0: derive N from the target's CapacityPartition; "
                         "default: whole-prompt admission)")
    ap.add_argument("--speculate-tokens", type=int, default=None,
                    metavar="K",
                    help="self-drafting speculative decoding: propose up to "
                         "K draft tokens per slot per drain boundary and "
                         "verify them in one batched forward "
                         "(0: derive K from the target's CapacityPartition; "
                         "default: off)")
    args = ap.parse_args(argv)
    if args.speculate_tokens is not None and not args.stream:
        ap.error("--speculate-tokens applies to --stream serving")
    if args.paged and not args.stream:
        ap.error("--paged applies to --stream serving")
    if args.prefix_share and not args.paged:
        ap.error("--prefix-share requires --paged (shared pages live in "
                 "the paged pool)")
    if args.disaggregate and not args.paged:
        ap.error("--disaggregate requires --paged (page handover moves "
                 "block-table rows, which the dense pool does not have)")
    if args.kv_quant and not args.paged:
        ap.error("--kv-quant requires --paged (tier codecs apply to the "
                 "paged pool's page bytes)")
    if args.park_idle is not None and not args.paged:
        ap.error("--park-idle requires --paged (the layer-2 host tier "
                 "serializes pages, which the dense pool does not have)")

    cfg = get_config(args.arch) if args.full else get_reduced(args.arch)
    if args.stream and (cfg.family == "encdec" or cfg.frontend_len):
        ap.error(f"--stream serves decoder-only token-prompt models; "
                 f"{cfg.name} ({cfg.family}) goes through one-shot mode")
    enable_compile_cache()
    dev = describe_devices()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    mesh = make_cli_mesh(args.mesh, args.mesh_axes)
    data_shards = shd.axis_size(mesh, shd.DATA_AXIS)
    model_shards = shd.axis_size(mesh, shd.MODEL_AXIS)

    target = args.target or device_target()
    tgt_ctx = use_target(target) if target else contextlib.nullcontext()
    with tgt_ctx, jax.set_mesh(mesh):
        model = build_model(cfg)
        params = init_serving_params(model, jax.random.PRNGKey(0), mesh)
        max_len = args.prompt_len + args.gen_len + cfg.frontend_len
        spec_k = args.speculate_tokens
        if spec_k == 0:
            spec_k = derive_speculate_tokens(cfg)
        engine = Engine(model, params,
                        EngineConfig(max_len=max_len,
                                     sync_interval=args.sync_interval,
                                     speculate_tokens=spec_k or 0,
                                     disaggregate=args.disaggregate,
                                     mesh=mesh))

        if args.stream:
            pages = None
            if args.paged:
                layer0, layer1 = args.layer0_bytes, args.layer1_bytes
                if layer0 is None and layer1 is None:
                    layer0, layer1 = pool_bytes_beside(params)
                pages = derive_page_geometry(
                    cfg, max_len, page_tokens=args.page_tokens,
                    max_slots=max(2, args.batch),
                    layer0_bytes=layer0, layer1_bytes=layer1,
                    model_shards=model_shards,
                    kv_quant=args.kv_quant)
            n_slots = args.slots or derive_n_slots(
                cfg, max_len, max_slots=max(2, args.batch), pages=pages,
                model_shards=model_shards, data_shards=data_shards)
            chunk = args.chunk_prefill_tokens
            if chunk == 0:
                chunk = derive_prefill_chunk(cfg)
            sched = Scheduler(n_slots=n_slots, pages=pages,
                              prefix_share=args.prefix_share,
                              chunk_prefill_tokens=chunk,
                              disaggregate=args.disaggregate)
            if args.prefix_share:
                system_len = args.system_len or max(1, args.prompt_len // 2)
                if system_len >= args.prompt_len:
                    ap.error("--system-len must leave room for a unique "
                             "tail (< --prompt-len)")
                stream = shared_prefix_stream(
                    args.stream, system_len, args.prompt_len - system_len,
                    args.gen_len, cfg.vocab_size)
            elif spec_k:
                # the motif-tiled workload the prompt-lookup proposer is
                # built for — what serve_bench --speculate measures
                stream = repetitive_stream(args.stream, args.prompt_len,
                                           args.gen_len, cfg.vocab_size)
            else:
                stream = synthetic_stream(args.stream, args.prompt_len,
                                          args.gen_len, cfg.vocab_size)
            rec = run_stream(engine, sched, stream,
                             park_idle=args.park_idle or 0)
            mode = ("paged+share" if args.prefix_share
                    else "paged" if args.paged else "dense")
            if args.disaggregate:
                mode += "+disagg"
            if args.kv_quant:
                mode += f"+{args.kv_quant}"
            print(f"arch={cfg.name} stream={args.stream} mode={mode} "
                  f"slots={rec['n_slots']} (max reuse {rec['max_slot_reuse']})")
            if data_shards * model_shards > 1:
                shards = kv_shards(cfg, model_shards)
                line = (f"mesh: {data_shards}x{model_shards} (data x model), "
                        f"kv pool sharded {shards}x")
                if args.paged:
                    line += f"; per-shard pool {rec['pool_bytes'] // shards} B"
                print(line)
            print(f"completed {rec['completed']}/{rec['n_requests']} "
                  f"({rec['n_tokens']} tokens) in {rec['wall_s']*1e3:.0f} ms "
                  f"-> {rec['tok_per_s']:.1f} tok/s")
            print(f"host syncs {rec['host_syncs']} over "
                  f"{rec['decode_steps_total']} decode steps")
            print(f"latency (decode steps): ttft p50/p95 "
                  f"{rec['ttft_steps_p50']:.0f}/{rec['ttft_steps_p95']:.0f}, "
                  f"e2e p50/p95 {rec['e2e_steps_p50']:.0f}/"
                  f"{rec['e2e_steps_p95']:.0f}, decode p50/p95 "
                  f"{rec['decode_steps_p50']:.0f}/"
                  f"{rec['decode_steps_p95']:.0f}")
            if rec["chunk_prefill_tokens"]:
                print(f"chunked prefill: {rec['chunk_prefill_tokens']} "
                      f"tokens/boundary budget, {rec['prefill_chunks']} "
                      f"chunks, max boundary prefill "
                      f"{rec['max_boundary_prefill_tokens']} tokens, "
                      f"ttft-to-first-token p50/p95 "
                      f"{rec['ttft_emit_steps_p50']:.0f}/"
                      f"{rec['ttft_emit_steps_p95']:.0f}")
            if spec_k:
                print(f"speculative decoding: k={rec['speculate_tokens']}, "
                      f"{rec['spec_proposed']} proposed -> "
                      f"{rec['spec_accepted']} accepted / "
                      f"{rec['spec_rejected']} rejected "
                      f"(acceptance {rec['spec_acceptance_rate']:.2f}); "
                      f"{rec['n_tokens']} tokens over "
                      f"{rec['decode_steps_total']} verify forwards")
            if args.disaggregate:
                roles = rec["host_syncs_by_role"]
                print(f"disaggregated roles: {rec['handovers']} handovers "
                      f"({rec['handover_pages']} pages moved, 0 bytes "
                      f"copied); host syncs prefill "
                      f"{roles.get('prefill', 0)} / decode "
                      f"{roles.get('decode', 0)}; "
                      f"{rec['decode_tokens']} decode-role tokens")
            if args.paged:
                print(f"pages: {rec['pages_high_water']}/{rec['n_pages']} "
                      f"layer-0 high water ({rec['pool_bytes']} B), "
                      f"{rec['preemptions']} preemptions -> "
                      f"{rec['spilled_pages']} pages spilled, "
                      f"{rec['restores']} restores "
                      f"(layer-1 high water {rec['spill_high_water']}/"
                      f"{rec['n_spill_pages']})", flush=True)
                if args.kv_quant:
                    print(f"tier codecs: layer0={rec['layer0_codec']} "
                          f"layer1={rec['layer1_codec']}; "
                          f"{rec['resident_high_water']} residents high "
                          f"water in {rec['pool_bytes']} B", flush=True)
                if args.park_idle is not None:
                    print(f"host parking: {rec['parks']} parked at step "
                          f"{args.park_idle}, {rec['park_resumes']} "
                          f"resumed (re-admitted as resumes, not "
                          f"re-prefills)", flush=True)
                if args.prefix_share:
                    hw = max(rec["pages_high_water"], 1)
                    print(f"prefix sharing: {rec['prefix_hits']} hits / "
                          f"{rec['prefix_misses']} misses, "
                          f"{rec['shared_prefix_tokens']} prompt tokens "
                          f"served from cache, {rec['cow_copies']} COW "
                          f"copies; residency {rec['mapped_high_water']} "
                          f"mapped vs {rec['pages_high_water']} physical "
                          f"pages ({rec['mapped_high_water'] / hw:.2f}x)",
                          flush=True)
            else:
                print(f"preemptions {rec['preemptions']} (dense pool)",
                      flush=True)
            return 0

        key = jax.random.PRNGKey(1)
        batch = {"tokens": jax.random.randint(
            key, (args.batch, args.prompt_len), 2, cfg.vocab_size)}
        if cfg.family == "encdec":
            batch["src_embeds"] = (jax.random.normal(
                key, (args.batch, args.prompt_len, cfg.d_model)) * 0.02
            ).astype(jnp.bfloat16)
        elif cfg.frontend_len:
            batch["frontend_embeds"] = (jax.random.normal(
                key, (args.batch, cfg.frontend_len, cfg.d_model)) * 0.02
            ).astype(jnp.bfloat16)

        t0 = time.monotonic()
        tokens, _ = engine.generate(batch, n_steps=args.gen_len)
        dt = time.monotonic() - t0
        n_generated = int(tokens.shape[0] * tokens.shape[1])
        print(f"arch={cfg.name} batch={args.batch} "
              f"prompt={args.prompt_len} gen={tokens.shape[1]}")
        print(f"tokens (first row): {tokens[0].tolist()}")
        print(f"total {dt*1e3:.0f} ms, {n_generated/dt:.1f} tok/s "
              f"(prefill amortized; {engine.last_stats['host_syncs']} host "
              f"syncs / {engine.last_stats['decode_steps']} steps)",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
