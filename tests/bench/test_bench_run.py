"""The command end to end on the CPU at the tiny size: it refuses to run
without a TPU or without the program; a sound run comes out correct, and
a run whose served tokens are altered where they are produced, whose
window holds a compile, or whose tokens are the fp8 control's, does not;
a configuration, a mix, a metric and a model family added as new files
are found by name; the four-chip cell runs on a mesh of four CPU devices,
and a mesh that does not fit the cell or its heads is refused.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import bench.run as run_mod
from bench import harness, traffic
from bench_helpers import REPO, cpu_device
from repro.serve import engine as engine_mod
from repro.serve import scheduler as sched_mod
from repro.serve.pool import pool_bytes_beside

ARGS = ["--seed", "3000000019", "--seconds", "2"]


def result_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def drive(root, workload, capsys, trace=0) -> dict:
    rc = run_mod.main(["--workload", workload, *ARGS, "--trace", str(trace)],
                      root=root, check_device=cpu_device, cache_dir=None)
    assert rc == 0
    return result_line(capsys)


def only_benchmark_files(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths."""
    root = tmp_path / "bare"
    root.mkdir()
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", root)
    for p in spec["paths"]:
        shutil.copytree(REPO / p, root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return root


@pytest.mark.parametrize("where", ["checkout", "benchmark_files_only"])
def test_exits_nonzero_without_a_tpu_or_the_program(where, tmp_path):
    root = REPO if where == "checkout" else only_benchmark_files(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2.5-3b.chat",
         *ARGS, "--trace", "0"], cwd=root, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_sound_run_is_correct_and_reports_its_metrics(tiny_root, capsys):
    res = drive(tiny_root, "qwen2.5-3b.chat", capsys)
    assert res["correct"] is True and res["attempted"] > 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert res["device"]["kind"] == "TPU v5 lite"
    assert list(res)[-1] == "checks"
    gap = res["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"]


def _altered_token(fn):
    """A served token changed where the decode chunk produces it."""
    def run(params, pool):
        pool, toks, valid = fn(params, pool)
        return pool, (toks + 1) % 512, valid
    return run


def _state_unchanged(fn):
    """The decode chunk hands back the KV pages it was given: what its
    steps wrote to the cache is lost."""
    def run(params, pool):
        kept = jax.tree.map(jnp.copy, pool.state)     # the pool is donated
        new, toks, valid = fn(params, pool)
        return dataclasses.replace(new, state=kept), toks, valid
    return run


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["altered_token", "state_unchanged"])
def test_broken_timed_path_is_caught(fault, tiny_root, capsys, monkeypatch):
    chunk = engine_mod.DecodeRole.pool_chunk
    monkeypatch.setattr(engine_mod.DecodeRole, "pool_chunk",
                        lambda self, n: fault(chunk(self, n)))
    res = drive(tiny_root, "qwen2.5-3b.chat", capsys)
    assert res["correct"] is False
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_new_config_mix_and_metric_are_found_by_name(tiny_root, capsys):
    """Add a cell from new files only; BENCHMARK.json gains entries."""
    bench = tiny_root / "bench"
    cfg = json.loads((bench / "configs/qwen2.5-3b.json").read_text())
    cfg.update(name="untied-tiny")
    cfg["model"]["tie_word_embeddings"] = False
    (bench / "configs/untied-tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic/chat.json").read_text())
    mix.update(rate_per_s=12.0, block=8)
    (bench / "traffic/busier.json").write_text(json.dumps(mix))
    (bench / "metrics/served_tokens.py").write_text(
        "def read(r):\n    return float(r.output_tokens())\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "untied-tiny", "source": "test",
                            "file": "bench/configs/untied-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "untied-tiny.busier",
                              "config": "untied-tiny", "traffic": "busier",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("untied-tiny.busier")
    spec["per_layer"].append({"name": "served_tokens", "unit": "tokens",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine loop (host)",
                              "moves": "itl_p95_ms",
                              "workloads": ["untied-tiny.busier"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    res = drive(tiny_root, "untied-tiny.busier", capsys, trace=1)
    assert res["correct"] is True
    # per-layer metrics report only in the cells they list
    assert set(res["metrics"]) == {"served_tokens"}
    assert res["metrics"]["served_tokens"]["value"] > 0


def run_cell(root, workload, **kw) -> harness.Result:
    """One run of ``workload`` through the harness, at the tiny size."""
    spec = harness.load_benchmark(root)
    cell, entry = harness.find_cell(spec, workload)
    config = json.loads((root / entry["file"]).read_text())
    mix = traffic.load_mix(cell["traffic"], root / "bench" / "traffic")
    return harness.run(spec=spec, cell=cell, config=config, mix=mix,
                       seed=3000000019, seconds=2.0, trace=False,
                       device=cpu_device(1), t_start=time.perf_counter(),
                       compiles=harness.CompileLog(), log=lambda s: None,
                       **kw)


def test_fp8_control_in_the_programs_place_is_not_correct(tiny_root):
    res = run_cell(tiny_root, "qwen2.5-3b.chat", control=True)
    assert res.correct is False
    assert res.checks["logit_gap"]["value"] == res.gaps["control"]["widest"]
    assert res.gaps["control"]["widest"] > res.checks["logit_gap"]["limit"]
    assert res.gaps["program"]["widest"] <= res.checks["logit_gap"]["limit"]


def test_a_compile_inside_the_window_is_not_correct(tiny_root, monkeypatch):
    """Set-up that warms nothing leaves the admissions and the decode
    chunk to compile while the window runs."""
    monkeypatch.setattr(harness, "warm", lambda *a: jax.clear_caches())
    res = run_cell(tiny_root, "qwen2.5-3b.code")
    assert res.checks["compiles_in_window"]["value"] > 0
    assert res.correct is False


def _with_config(root, name, **serve):
    """The configuration ``name`` of ``root`` with its ``serve`` updated
    (a key set to ``None`` taken out)."""
    path = root / "bench" / "configs" / f"{name}.json"
    cfg = json.loads(path.read_text())
    cfg["serve"].update(serve)
    cfg["serve"] = {k: v for k, v in cfg["serve"].items() if v is not None}
    path.write_text(json.dumps(cfg))


def test_a_cell_whose_heads_do_not_divide_is_refused(tiny_root):
    """The tiny qwen2.5-3b has 2 KV heads: a 4-way model axis cannot
    split them, and the refusal comes before any device is touched."""
    _with_config(tiny_root, "qwen2.5-3b", mesh={"data": 1, "model": 4})
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"][0]["chips"] = 4
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(ValueError, match=r"2 KV heads do not divide over "
                                         r"the 4-way model axis"):
        run_cell(tiny_root, spec["workloads"][0]["name"])


@pytest.mark.parametrize("mesh, chips, match", [
    (None, 4, "needs a mesh"),
    ({"data": 1, "model": 2}, 4, "spans 2 devices"),
], ids=["no_mesh_on_four_chips", "mesh_not_the_cells_chips"])
def test_a_mesh_that_is_not_the_cells_chips_is_refused(tiny_root, mesh,
                                                       chips, match):
    _with_config(tiny_root, "mistral-nemo-12b-tp4", mesh=mesh)
    with pytest.raises(ValueError, match=match):
        harness.make_mesh(json.loads(
            (tiny_root / "bench/configs/mistral-nemo-12b-tp4.json")
            .read_text()), chips)


def test_a_configuration_without_a_mesh_builds_as_on_one_chip(tiny_root):
    """No mesh: no mesh object, the weights on one device, and the page
    geometry the pool's byte budget beside them gives unsharded."""
    config = json.loads(
        (tiny_root / "bench/configs/qwen2.5-3b.json").read_text())
    assert "mesh" not in config["serve"]
    mix = traffic.load_mix("chat", tiny_root / "bench" / "traffic")
    served = harness.build(config, mix, 3000000019,
                           families=tiny_root / "bench" / "families")
    assert served.mesh is None and served.engine.ecfg.mesh is None
    assert all(len(leaf.sharding.device_set) == 1
               for leaf in jax.tree.leaves(served.params))
    layer0, layer1 = pool_bytes_beside(served.params)
    cfg = served.family.model_config(config)
    assert served.geom == sched_mod.derive_page_geometry(
        cfg, config["serve"]["max_len"], layer0_bytes=layer0,
        layer1_bytes=layer1)


@pytest.mark.parametrize("fault", [None, "spill", "no_exchange",
                                   "fp8_control"],
                         ids=["sound", "spilling", "exchange_left_out",
                              "fp8_control"])
def test_the_four_chip_cell_on_a_mesh(fault, tmp_path):
    """The tiny mistral-nemo-12b-tp4.chat-batch through ``main`` on four
    CPU devices, its weights and KV pages split four ways and its
    end-to-end metrics reported: correct, also where a pool of eight
    pages makes sequences spill and come back; not correct where each
    chip keeps its share of the down projection to itself or where the
    fp8 reference's picks stand in the program's place."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests/bench/mesh_run_check.py"),
         str(tmp_path / "checkout"), *([fault] if fault else [])],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    res, lay = json.loads(lines[-2]), json.loads(lines[-1])
    sound = fault in (None, "spill")
    assert res["correct"] is sound and res["attempted"] > 0
    gap = res["checks"]["logit_gap"]
    assert (gap["value"] <= gap["limit"]) is sound
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert (lay["spills"] > 0 and lay["restores"] > 0) is (fault == "spill")
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert res["device"]["count"] == 4
    for tree in ("weights", "pool"):
        assert lay[tree] == {"devices": 4, "largest_share": 0.25}, lay


def test_a_family_file_is_found_by_name(tiny_root, capsys):
    """A configuration names a family added as a new file; the run
    checks its outputs through that file."""
    fam = tiny_root / "bench" / "families"
    (fam / "dense_marked.py").write_text(
        (fam / "dense.py").read_text()
        + "\n\ndef token_gaps(*a, **k):\n"
          "    from pathlib import Path\n"
          "    Path(__file__).with_suffix('.used').touch()\n"
          "    return reference.token_gaps(*a, **k)\n")
    path = tiny_root / "bench/configs/qwen2.5-3b.json"
    cfg = json.loads(path.read_text())
    cfg["family"] = "dense_marked"
    path.write_text(json.dumps(cfg))
    res = drive(tiny_root, "qwen2.5-3b.chat", capsys)
    assert res["correct"] is True
    assert (fam / "dense_marked.used").is_file()


def test_an_unknown_family_is_refused(tiny_root):
    path = tiny_root / "bench/configs/qwen2.5-3b.json"
    cfg = json.loads(path.read_text())
    cfg["family"] = "no-such-family"
    path.write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match=r"families/no-such-family\.py"):
        run_cell(tiny_root, "qwen2.5-3b.chat")
