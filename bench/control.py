#!/usr/bin/env python3
"""Readings that set a cell's output-check limit, on the chip. For each
seed, in one process:

* one run of the cell as ``run.py`` makes it, with the plain reference in
  the program's place at fp8 over the same prompts and served tokens: the
  program's gaps (the lower reading) and the fp8 reference's, which the
  run judges (``fp8_reference_correct`` must come out false);
* with ``--kv-quant``, one more run of the cell with the program's own
  quantized KV pages switched on, checked as a sound run is; a run that
  crashes is a failed control and prints its error.

Each gap summary gives the widest and mean gap below the reference's best
logit and the share of tokens off the reference's argmax.

The benchmark's own runs do not run the control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51 \
        [--kv-quant fp8]
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--kv-quant", default=None)
    args = ap.parse_args(argv)
    import jax
    from bench import harness, traffic
    from bench.run import CACHE_DIR, device_check
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spec = harness.load_benchmark(ROOT)
    cell, entry = harness.find_cell(spec, args.workload)
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = traffic.load_mix(cell["traffic"])
    device = device_check(cell["chips"])
    compiles = harness.CompileLog()

    def one(seed, **kw):
        # the last run's engine, weights and pool sit in reference cycles
        # and compiled-function caches: free them before this run builds
        # its own, or the chip runs out of memory
        jax.clear_caches()
        gc.collect()
        return harness.run(spec=spec, cell=cell, config=config, mix=mix,
                           seed=seed, seconds=args.seconds, trace=False,
                           device=device, t_start=time.perf_counter(),
                           compiles=compiles, **kw)

    for seed in (int(s) for s in args.seeds.split(",")):
        res = one(seed, control=True)
        print(json.dumps({"seed": seed, "program": res.gaps["program"],
                          "fp8_reference": res.gaps["control"],
                          "fp8_reference_correct": res.correct,
                          "notes": res.notes, "device": device["kind"]}),
              flush=True)
        if args.kv_quant:
            key = f"kv_{args.kv_quant}"
            try:
                kv = one(seed, kv_quant=args.kv_quant)
                row = {key: kv.gaps["program"], f"{key}_correct": kv.correct,
                       "notes": kv.notes}
            except Exception as e:      # a control that crashes has failed
                row = {f"{key}_correct": False, "error": repr(e)[:400]}
            print(json.dumps({"seed": seed, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
