#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout; the cell, its configuration, its traffic
mix and its per-layer metric readers are found by name from
``BENCHMARK.json`` (``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py``), and the
configuration's model family by its ``family``
(``bench/families/<family>.py``). A configuration whose ``serve`` names a
``mesh`` is served tensor-parallel over that many chips.

The run needs a TPU whose ``device_kind`` has a published peak in
``bench/peaks.py`` and as many chips as the cell asks for; anything else
exits non-zero before any result is printed. Set-up builds the weights
from the seed, warms every program the cell's traffic can run, then runs
the arrival process for the mix's warm-in; the window follows. After it,
a sample of finished requests is checked against the plain reference.

Earlier lines name the device and count compiles inside the window (it
must be 0). The last line of standard output is the result, one JSON
object; the last lines of standard error give each number compared
beside its limit. JAX's compilation cache lives at
``.bench_cache/jax`` inside the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
CACHE_DIR = ROOT / ".bench_cache" / "jax"


def device_check(chips: int) -> dict:
    """Platform, kind and count of the devices; exits unless they are TPUs
    of a kind with a published peak, at least ``chips`` of them."""
    import jax
    from bench.peaks import PEAKS
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"run.py: needs a TPU, JAX reports "
                         f"{dev['platform']}")
    if dev["kind"] not in PEAKS:
        raise SystemExit(f"run.py: no published peak for {dev['kind']!r}")
    if dev["count"] < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips, JAX "
                         f"reports {dev['count']}")
    return dev


def load_readers(names, directory: Path) -> dict:
    """``read`` of each per-layer metric's own reader file."""
    from bench.harness import load_module
    out = {}
    for name in names:
        path = directory / f"{name}.py"
        if not path.is_file():
            raise KeyError(f"no reader for metric {name!r} ({path})")
        out[name] = load_module(path, "bench_metric_").read
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="also write the reduced trace there as JSON")
    return ap.parse_args(argv)


def main(argv=None, *, root: Path = ROOT, check_device=device_check,
         cache_dir=CACHE_DIR) -> int:
    """``root``, ``check_device`` and ``cache_dir`` (``None``: leave JAX's
    cache alone) let a test drive a run without a chip."""
    args = parse(argv)
    import jax
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness, traffic
    spec = harness.load_benchmark(root)
    cell, entry = harness.find_cell(spec, args.workload)
    config = json.loads((root / entry["file"]).read_text())
    mix = traffic.load_mix(cell["traffic"], root / "bench" / "traffic")
    device = check_device(cell["chips"])
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    compiles = harness.CompileLog()
    readers = None
    if args.trace:
        readers = load_readers(
            [m["name"] for m in harness.metrics_for(spec, cell["name"], True)],
            root / "bench" / "metrics")
    result = harness.run(spec=spec, cell=cell, config=config, mix=mix,
                         seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), device=device,
                         t_start=T_START, compiles=compiles, readers=readers,
                         keep_trace=args.keep_trace,
                         families=root / "bench" / "families",
                         log=lambda s: print(s, flush=True))
    for note in result.notes:
        print(note, flush=True)
    for name, c in result.checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(result.line(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
