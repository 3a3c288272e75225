#!/usr/bin/env python3
"""Find a cell's knee: serve its mix at several fixed rates in one
process (one set-up, one compile) and print, per rate, what the system
sustained. Run on the chip when a cell is defined; the cell then offers a
fixed rate, about four fifths of the knee.

    python3 bench/sweep.py --workload <cell> --rates 0.3,0.5,0.7 \
        --seconds 40 [--seed 1]

Each line is one JSON object: the rate, requests due and admitted in the
window, the queue left at its close, completions, the TTFT and
inter-token percentiles, output tokens per second and the mean lifetime
(due to last token) of the requests that finished.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
#: seconds past the close a request due in the window may take for its
#: first token: short, so an overloaded rate does not hold the sweep for
#: the mix's own cap
DRAIN_CAP_S = 5.0
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    from bench import harness, traffic
    from bench.gate import Gate
    from bench.readings import Readings, percentile
    from bench.run import CACHE_DIR, device_check
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    spec = harness.load_benchmark(ROOT)
    cell, entry = harness.find_cell(spec, args.workload)
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = traffic.load_mix(cell["traffic"])
    device = device_check(cell["chips"])
    served = harness.build(config, mix, args.seed, chips=cell["chips"])
    harness.warm(served, config, mix)
    serve = config["serve"]
    for rate in (float(x) for x in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        gate = Gate(n_slots=serve["n_slots"], pages=served.geom,
                    arrivals=traffic.arrivals(m, args.seed,
                                              config["model"]["vocab_size"]),
                    warm_in_s=m["warm_in_s"], seconds=args.seconds,
                    drain_cap_s=DRAIN_CAP_S)
        served.engine.serve(scheduler=gate)
        served.engine._last_pool = served.engine._last_spill = None
        r = Readings(gate=gate, seconds=args.seconds,
                     shape=config["model"], n_slots=serve["n_slots"],
                     sync_interval=served.engine.ecfg.sync_interval,
                     chips=cell["chips"], kind=device["kind"])
        due = r.due_in_window()
        admitted = [x for x in due if 0 <= x.admit_t <= gate.t_close]
        done = [x for x in gate.records.values()
                if x.n_tokens == x.max_new_tokens and x.deliveries]
        ttft, itl = r.ttfts_s(), r.itls_s()
        print(json.dumps({
            "rate_per_s": rate, "due": len(due), "admitted": len(admitted),
            "queue_at_close": sum(1 for x in due if x.admit_t < 0
                                  or x.admit_t > gate.t_close),
            "completed": len(done),
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p90_ms": 1e3 * percentile(ttft, 90),
            "itl_p50_ms": 1e3 * percentile(itl, 50),
            "itl_p95_ms": 1e3 * percentile(itl, 95),
            "output_tok_s": r.output_tokens() / args.seconds,
            "occupancy_pct": r.decode_occupancy_pct(),
            "lifetime_mean_s": (sum(x.deliveries[-1][0] - x.due for x in done)
                                / max(len(done), 1)),
            "device": device["kind"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
