"""Helpers of the benchmark's own tests: a tiny copy of the benchmark
that runs on the CPU, and what a v5e host reports."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

#: the qwen2.5-3b cells at a size a test run holds: 2 layers, d_model 64
TINY_MODEL = dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  num_hidden_layers=2, vocab_size=512)
#: the mistral-nemo-12b-tp4 cell at a size a test run holds: 2 layers,
#: an untied head, a head size that is not d_model / heads, and heads that
#: divide over its 4-way model axis
TINY_NEMO = dict(hidden_size=64, intermediate_size=128, head_dim=24,
                 num_attention_heads=8, num_key_value_heads=4,
                 num_hidden_layers=2, vocab_size=512)
TINY_SERVE = dict(max_len=128, n_slots=4, prompt_pad_multiple=16)
TINY_LENGTHS = dict(
    prompt={"dist": "lognormal", "median": 30, "sigma": 0.6, "min": 8,
            "max": 90},
    output={"dist": "lognormal", "median": 8, "sigma": 0.6, "min": 2,
            "max": 30})


def cpu_device(chips: int) -> dict:
    """What a v5e host of ``chips`` chips reports, for runs driven on the
    CPU."""
    return {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}


def make_tiny_root(root: Path) -> Path:
    """A benchmark checkout whose cells run at the tiny size: the
    repository's BENCHMARK.json, model families, metric readers and
    mixes, with the configurations cut and the mixes shortened."""
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    for d in ("metrics", "families"):
        shutil.copytree(REPO / "bench" / d, root / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    for name, model in (("qwen2.5-3b", TINY_MODEL),
                        ("mistral-nemo-12b-tp4", TINY_NEMO)):
        path = f"bench/configs/{name}.json"
        cfg = json.loads((REPO / path).read_text())
        cfg["model"].update(model)
        if "head_dim" in cfg["assumed"]:
            cfg["assumed"]["head_dim"] = 16
        cfg["serve"].update(TINY_SERVE)
        # at this size the program's widest gap reads about 0.004 and the
        # fp8 control's 0.07 (tests/bench/test_bench_reference.py)
        cfg["check"]["logit_gap_limit"] = 0.02
        (root / path).write_text(json.dumps(cfg))
    for path in (REPO / "bench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        mix.update(TINY_LENGTHS, warm_in_s=0.3, drain_cap_s=5,
                   check_tokens=40)
        if mix["arrival"] == "poisson":
            mix["rate_per_s"] = 8.0
        (root / "bench" / "traffic" / path.name).write_text(json.dumps(mix))
    return root

