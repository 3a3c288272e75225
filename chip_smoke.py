#!/usr/bin/env python3
"""Bring-up smoke: the paged serving path, end to end, on a TPU.

    python3 chip_smoke.py                 # one chip: qwen2.5-3b, 36 layers
    python3 chip_smoke.py --four-chips    # four chips: mistral-nemo-12b, 1x4 mesh

One process, every phase in it, run from the root of a checkout (the script
puts ``src/`` on the path itself). Weights are random, made from ``--seed``.

One chip (the default):

  * **device** — JAX must report a TPU whose ``device_kind`` maps to a
    registered hardware target; anything else exits non-zero before any
    result is printed. There is no CPU fallback.
  * **serve** — eight requests through the functions
    ``python -m repro.launch.serve --stream --paged`` uses: ``run_stream``
    over an ``Engine`` and a paged ``Scheduler`` (page_tokens 16), at the
    full published widths of qwen2.5-3b. Prompts run up to 4096 tokens, so
    prefill reaches the flash kernel's 2048 and 4096 sequence buckets;
    answers are 32 tokens. The pool gets what the device has left beside
    the weights (``pool_bytes_beside``), all of it: no slot-count cap, so
    the run proves that the budget fits.
  * **check** — every request drains with in-vocabulary tokens; each first
    token equals the engine's one-shot ``generate`` for the same prompt; the
    Pallas page walk matches the ``decode_attention_masked`` oracle at the
    served shapes within ``PAGE_WALK_ATOL + PAGE_WALK_RTOL * |oracle|``;
    the compiled admission and decode programs hold ``tpu_custom_call``
    (the kernels ran, not the gather/oracle route).

Four chips (``--four-chips``, only this phase): mistral-nemo-12b at full
width served on a 1x4 ``model`` mesh (its 8 KV heads shard the pages 4
ways). First tokens are compared with the same engine's mesh-4 one-shot
``generate``; the compiled mesh admission and decode programs must hold
``tpu_custom_call`` (the kernels ran per shard, not the jnp route); and
each device's ``bytes_in_use`` and weight bytes are printed: no device may
hold more than ``MAX_DEVICE_SKEW`` times the mean.

Earlier lines report compile seconds (``backend_compile_duration`` events,
persistent-cache hits included), stream wall time and tokens, peak device
bytes and the pages high-water mark, each naming the device. None of them
is a performance claim. The last line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.target import use_target  # noqa: E402
from repro.kernels.paged_attention import (  # noqa: E402
    decode_attention_masked, gather_kv_pages, paged_decode_attention)
from repro.launch.device import (describe_devices, device_target,  # noqa: E402
                                 enable_compile_cache)
from repro.launch.mesh import make_cli_mesh  # noqa: E402
from repro.launch.serve import run_stream  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve.engine import (Engine, EngineConfig,  # noqa: E402
                                init_serving_params)
from repro.serve.pool import pool_bytes_beside  # noqa: E402
from repro.serve.scheduler import Scheduler, derive_page_geometry  # noqa: E402

PAGE_TOKENS = 16
SYNC_INTERVAL = 8
#: one-chip stream: prompt lengths (the 2048 and 4096 flash buckets, plus
#: lengths the kernel entry pads up to a block multiple) and answer length
ONE_CHIP_PROMPTS = (4096, 2048, 1500, 333, 4000, 2048, 100, 3000)
ONE_CHIP_NEW = 32
ONE_CHIP_SLOTS = 4
FOUR_CHIP_PROMPTS = (2048, 1000, 512, 1536)
FOUR_CHIP_NEW = 16
#: page walk vs oracle, both bf16 out: the oracle's f32 PV product runs at
#: the TPU's default matmul precision (bf16 operands), the kernel's in f32,
#: and both round the result to bf16 — a few bf16 ulps of an O(1) value
PAGE_WALK_ATOL = 2e-2
PAGE_WALK_RTOL = 2e-2
MAX_DEVICE_SKEW = 1.5


class CompileClock:
    """Sums JAX's backend-compile events (a persistent-cache hit counts
    its retrieval time) and counts cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self, phase: str, dev: dict) -> str:
        return (f"compile [{phase}]: {self.seconds:.2f} s over "
                f"{self.programs} programs, {self.cache_hits} persistent "
                f"cache hits (device {dev['kind']} x{dev['count']})")


def make_stream(prompt_lens, max_new: int, vocab: int, seed: int) -> list:
    """Random prompts over ``[2, vocab)`` (0 is pad, 1 is EOS)."""
    rng = np.random.default_rng(seed)
    return [{"prompt": rng.integers(2, vocab, size=n).astype(np.int32),
             "max_new_tokens": max_new} for n in prompt_lens]


def serve_stream(cfg, stream: list, *, seed: int, n_slots: int,
                 max_len: int, mesh=None) -> dict:
    """Build weights, pool and engine the way ``launch/serve.py`` does and
    run ``stream`` through ``run_stream``."""
    model = build_model(cfg)
    params = init_serving_params(model, jax.random.PRNGKey(seed), mesh)
    layer0, layer1 = pool_bytes_beside(params)
    model_shards = 1 if mesh is None else mesh.shape["model"]
    geom = derive_page_geometry(cfg, max_len, page_tokens=PAGE_TOKENS,
                                layer0_bytes=layer0, layer1_bytes=layer1,
                                model_shards=model_shards)
    engine = Engine(model, params, EngineConfig(
        max_len=max_len, sync_interval=SYNC_INTERVAL, mesh=mesh))
    sched = Scheduler(n_slots=n_slots, pages=geom)
    rec = run_stream(engine, sched, stream)
    return {"engine": engine, "geom": geom, "rec": rec, "params": params}


def check_stream(cfg, stream: list, served: dict) -> str:
    """Every request drains, every token is in the vocabulary, and each
    first token equals the one-shot ``generate`` of its prompt."""
    rec, engine = served["rec"], served["engine"]
    assert rec["completed"] == len(stream), (rec["completed"], len(stream))
    outputs = rec["outputs"]
    for rid, spec in enumerate(stream):
        toks = outputs[rid]
        assert 1 <= len(toks) <= spec["max_new_tokens"], (rid, len(toks))
        assert all(0 <= t < cfg.vocab_size for t in toks), rid
        ref, _ = engine.generate(
            {"tokens": jnp.asarray(spec["prompt"])[None]}, n_steps=1)
        want = int(ref[0, 0])
        assert toks[0] == want, (
            f"request {rid} (prompt {len(spec['prompt'])}): served first "
            f"token {toks[0]} != one-shot generate {want}")
    return (f"{len(stream)} requests drained, tokens in vocabulary, first "
            f"tokens equal one-shot generate")


def check_page_walk(cfg, geom, n_slots: int, seed: int) -> str:
    """Pallas page walk vs gather + oracle at the served pool shapes."""
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p_max, pt = geom.max_pages_per_slot, geom.page_tokens
    shape = (geom.n_pages, hkv, pt, hd)
    q = jax.random.normal(keys[0], (n_slots, hq, 1, hd), jnp.bfloat16)
    k = jax.random.normal(keys[1], shape, jnp.bfloat16)
    v = jax.random.normal(keys[2], shape, jnp.bfloat16)
    rng = np.random.default_rng(seed)
    tables = np.stack([rng.choice(np.arange(1, geom.n_pages), p_max,
                                  replace=False) for _ in range(n_slots)])
    lens = rng.integers(1, p_max * pt, size=n_slots)
    tables, lens = jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32)
    got = jax.jit(paged_decode_attention)(q, k, v, tables, lens)

    @jax.jit
    def oracle(q, k, v, tables, lens):
        return decode_attention_masked(
            q, gather_kv_pages(k, tables, seq_axis=1),
            gather_kv_pages(v, tables, seq_axis=1), lens)

    want = np.asarray(oracle(q, k, v, tables, lens), np.float32)
    got = np.asarray(got, np.float32)
    err = np.abs(got - want)
    bound = PAGE_WALK_ATOL + PAGE_WALK_RTOL * np.abs(want)
    assert np.isfinite(got).all() and (err <= bound).all(), (
        f"page walk differs from the oracle by up to {err.max():.4g}")
    return (f"page walk vs oracle at B={n_slots} P={p_max} pt={pt}: max "
            f"|diff| {err.max():.6g} <= {PAGE_WALK_ATOL} + "
            f"{PAGE_WALK_RTOL}*|oracle|")


def check_custom_calls(served: dict, stream: list) -> str:
    """The admission and decode programs that served the stream contain the
    Pallas kernels (``tpu_custom_call``)."""
    engine, geom = served["engine"], served["geom"]
    pool = engine._last_pool
    admit = engine.core._paged_admit_fns[(geom.depth, geom.page_tokens)]
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    prompt = jnp.asarray(stream[0]["prompt"])[None]
    programs = {
        "admit": admit.lower(engine.params, prompt, i32(prompt.shape[1]),
                             i32(1), i32(0),
                             jnp.zeros((geom.max_pages_per_slot,), jnp.int32),
                             pool),
        "decode": engine.decode_role.pool_chunk(SYNC_INTERVAL).lower(
            engine.params, pool),
    }
    for name, lowered in programs.items():
        assert "tpu_custom_call" in lowered.compile().as_text(), (
            f"the compiled {name} program holds no Pallas kernel")
    return "compiled admission and decode programs hold tpu_custom_call"


def check_spread(served: dict, devices: list) -> str:
    """Every device holds a share of the weights, and none holds much more
    than the mean of weights or of ``bytes_in_use``."""
    weights = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(served["params"]):
        for s in leaf.addressable_shards:
            weights[s.device] += s.data.nbytes
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices]
    for d, used in zip(devices, in_use):
        print(f"device {d.id}: bytes_in_use {used}, weights {weights[d]} B",
              flush=True)
    assert all(weights[d] > 0 for d in devices), "a device holds no weights"
    for name, per in (("bytes_in_use", in_use),
                      ("weights", [weights[d] for d in devices])):
        mean = sum(per) / len(per)
        assert max(per) <= MAX_DEVICE_SKEW * mean, (
            f"{name}: one device holds {max(per)} B against a mean of "
            f"{mean:.0f} B")
    return (f"{len(devices)} devices each hold weights; max/mean "
            f"bytes_in_use {max(in_use) / max(sum(in_use) / len(in_use), 1):.4f}")


def run_checks(checks) -> list:
    """Run each ``(name, check)``; a failed check is reported with its
    traceback and the next one still runs. Returns the failed names."""
    failed = []
    for name, check in checks:
        try:
            line = check()
        except Exception:   # report every failed phase, not just the first
            traceback.print_exc()
            print(f"FAILED [{name}]", flush=True)
            failed.append(name)
        else:
            print(f"check [{name}]: {line}", flush=True)
    return failed


def memory_line(phase: str, dev: dict) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    return (f"memory [{phase}]: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use')} of bytes_limit "
            f"{stats.get('bytes_limit')} (device {dev['kind']} "
            f"x{dev['count']})")


def stream_line(cfg, served: dict, dev: dict) -> str:
    rec, geom = served["rec"], served["geom"]
    return (f"stream [{cfg.name}, {cfg.n_layers} layers]: {rec['completed']}/"
            f"{rec['n_requests']} requests, {rec['n_tokens']} tokens in "
            f"{rec['wall_s']:.3f} s wall (compiles included); pages high "
            f"water {rec['pages_high_water']}/{geom.n_pages - 1} layer-0, "
            f"{rec['preemptions']} preemptions (device {dev['kind']} "
            f"x{dev['count']})")


def one_chip(dev: dict, clock: CompileClock, seed: int) -> list:
    cfg = get_config("qwen2.5-3b")
    max_len = max(ONE_CHIP_PROMPTS) + ONE_CHIP_NEW
    stream = make_stream(ONE_CHIP_PROMPTS, ONE_CHIP_NEW, cfg.vocab_size, seed)
    served = serve_stream(cfg, stream, seed=seed, n_slots=ONE_CHIP_SLOTS,
                          max_len=max_len)
    print(clock.line("serve", dev), flush=True)
    print(stream_line(cfg, served, dev), flush=True)
    print(memory_line("serve", dev), flush=True)
    failed = run_checks([
        ("stream", lambda: check_stream(cfg, stream, served)),
        ("page walk", lambda: check_page_walk(cfg, served["geom"],
                                              ONE_CHIP_SLOTS, seed)),
        ("kernels", lambda: check_custom_calls(served, stream)),
    ])
    print(clock.line("total", dev), flush=True)
    return failed


def four_chips(dev: dict, clock: CompileClock, seed: int) -> list:
    cfg = get_config("mistral-nemo-12b")
    mesh = make_cli_mesh("4")
    max_len = max(FOUR_CHIP_PROMPTS) + FOUR_CHIP_NEW
    stream = make_stream(FOUR_CHIP_PROMPTS, FOUR_CHIP_NEW, cfg.vocab_size,
                         seed)
    with jax.set_mesh(mesh):
        served = serve_stream(cfg, stream, seed=seed,
                              n_slots=len(FOUR_CHIP_PROMPTS),
                              max_len=max_len, mesh=mesh)
        print(clock.line("serve", dev), flush=True)
        print(stream_line(cfg, served, dev), flush=True)
        failed = run_checks([
            ("stream", lambda: check_stream(cfg, stream, served)),
            ("kernels", lambda: check_custom_calls(served, stream)),
            ("spread", lambda: check_spread(served, list(mesh.devices.flat))),
        ])
    print(clock.line("total", dev), flush=True)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (mistral-nemo-12b "
                         "on a 1x4 mesh)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    clock = CompileClock()
    dev = describe_devices()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX reports {dev['platform']}",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if dev["count"] < need:
        print(f"chip_smoke: needs {need} chips, JAX reports {dev['count']}",
              file=sys.stderr)
        return 2
    target = device_target()
    print(f"target: {target.name}", flush=True)
    with use_target(target):
        failed = (four_chips if args.four_chips else one_chip)(
            dev, clock, args.seed)
    if failed:
        print(f"chip_smoke: failed {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
