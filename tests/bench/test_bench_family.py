"""A model family is a plug-in of the harness: its weight rules, work
counts, kernel and program names and sharded counts are taken where it
gives them, and the dense family, which gives none, leaves the weights
and readings as they were. A family of another kind (MLA + MoE, kept in
``tests/bench/plugin/``) is served through ``bench/run.py`` from new
files only.
"""

from __future__ import annotations

import hashlib
import json
import shutil
import types
from pathlib import Path

import jax
import numpy as np
import pytest

import bench.run as run_mod
from bench import harness, peaks, readings, traffic, weights
from bench import trace as trace_mod
from bench_helpers import REPO, TINY_MODEL, TINY_NEMO, cpu_device
from repro.kernels.paged_attention import paged_flash_decode
from repro.models import build_model

PLUGIN = Path(__file__).resolve().parent / "plugin"
TESTDATA = REPO / "bench" / "testdata"
SEED = 3000000019
CELL = "mla-moe-tiny.chat"

#: sha256 of ``weights.make_params`` at the tiny dense sizes of
#: ``bench_helpers``, taken on the tree before families could bring weight
#: rules: every leaf's path, type, shape and bytes in tree order
DENSE_DIGESTS = {
    ("qwen2.5-3b", 0):
        "6885d31472eb738caf73268f75c1fa650e5d042afd4ddb4146df89026fb29e8d",
    ("qwen2.5-3b", SEED):
        "239e8f5ea3a2c5c8d8f5ac9c342c15eeaf3c697a83c8a15ac569d4ae6404783a",
    ("mistral-nemo-12b-tp4", 0):
        "464e499611b0dc2f118238329855a29f8467757ef73e391ea6a020b25bf924f9",
    ("mistral-nemo-12b-tp4", SEED):
        "4382e0ae83c9475d6d70da5e0092241dd18400d2c06cc5a9ec796fada69fc511",
}


def digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name, seed", sorted(DENSE_DIGESTS),
                         ids=[f"{n}-{s}" for n, s in sorted(DENSE_DIGESTS)])
def test_dense_weights_are_unchanged(name, seed):
    config = json.loads((REPO / f"bench/configs/{name}.json").read_text())
    config["model"].update(TINY_MODEL if name == "qwen2.5-3b" else TINY_NEMO)
    if "head_dim" in config["assumed"]:
        config["assumed"]["head_dim"] = 16
    family = harness.load_family(config["family"])
    model = build_model(family.model_config(config))
    params = weights.make_params(model.init, seed, family=family)
    assert digest(params) == DENSE_DIGESTS[name, seed]


# ------------------------------------------------------ the MLA + MoE cell
def snapshot(root: Path) -> dict:
    return {p.relative_to(root).as_posix(): p.read_bytes()
            for p in root.rglob("*") if p.is_file()}


def add_mla_moe_cell(root: Path, family_text: str = None) -> None:
    """The family, its configuration, two per-layer metrics and the cell
    as new files, and the cell's entries in BENCHMARK.json."""
    bench = root / "bench"
    (bench / "families/mla_moe.py").write_text(
        family_text or (PLUGIN / "mla_moe.py").read_text())
    shutil.copy(PLUGIN / "mla-moe-tiny.json", bench / "configs")
    (bench / "metrics/mla_flash_roofline.py").write_text(
        "def read(r):\n    return r.roofline_pct('mla_flash')\n")
    (bench / "metrics/prefill_step_mfu.mla.py").write_text(
        "def read(r):\n    return r.step_mfu_pct('admit')\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "mla-moe-tiny", "source": "test",
                            "file": "bench/configs/mla-moe-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "mla-moe-tiny",
                              "traffic": "chat", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append(CELL)
    for name, layer in (("mla_flash_roofline", "kernels"),
                        ("prefill_step_mfu.mla", "model step")):
        spec["per_layer"].append({"name": name, "unit": "%",
                                  "better": "higher",
                                  "source": "device_trace", "layer": layer,
                                  "moves": "itl_p95_ms",
                                  "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_an_mla_moe_family_is_new_files_only(tiny_root, capsys,
                                             monkeypatch):
    """The cell runs through ``bench/run.py`` traced. A CPU run records no
    device op, so the harness is handed a trace recorded on a v5e in its
    place: the family's kernel is found in it under the family's name and
    priced with the family's counts."""
    before = snapshot(tiny_root)
    add_mla_moe_cell(tiny_root)
    recorded = trace_mod.load_json(TESTDATA / "v5e_chat_decode.json.gz")
    read = harness.Tracer.read

    def read_recorded(self, keep=None):
        read(self, keep)            # stops the profiler and cleans up
        return recorded

    monkeypatch.setattr(harness.Tracer, "read", read_recorded)
    rc = run_mod.main(["--workload", CELL, "--seed", str(SEED),
                       "--seconds", "2", "--trace", "1"],
                      root=tiny_root, check_device=cpu_device,
                      cache_dir=None)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(res["metrics"]) == {"mla_flash_roofline",
                                   "prefill_step_mfu.mla"}
    for m in res["metrics"].values():
        assert 0 < m["value"] < 100
    assert res["attempted"] > 0 and list(res)[-1] == "checks"
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert np.isfinite(res["checks"]["logit_gap"]["value"])
    assert res["device"]["busy_s"] > 0
    after = snapshot(tiny_root)
    changed = {p for p in before if after[p] != before[p]}
    assert changed == {"BENCHMARK.json"}
    # BENCHMARK.json only gained entries
    old = json.loads(before["BENCHMARK.json"])
    new = json.loads(after["BENCHMARK.json"])
    for key in ("configs", "workloads", "per_layer"):
        assert new[key][:len(old[key])] == old[key]
    for o, n in zip(old["end_to_end"], new["end_to_end"]):
        assert n in (o, {**o, "workloads": o.get("workloads", []) + [CELL]})


def test_a_leaf_no_rule_covers_is_refused(tiny_root):
    """A family whose rules leave ``wkv_a`` to the dense rules, which do
    not know it: refused with the family and the leaf's whole path."""
    add_mla_moe_cell(tiny_root, (PLUGIN / "mla_moe.py").read_text() + (
        "\n\n_rule = weight_rule\n\n\n"
        "def weight_rule(path, shape, key):\n"
        "    if path[-1] != 'wkv_a':\n"
        "        return _rule(path, shape, key)\n"))
    spec = harness.load_benchmark(tiny_root)
    cell, entry = harness.find_cell(spec, CELL)
    config = json.loads((tiny_root / entry["file"]).read_text())
    mix = traffic.load_mix("chat", tiny_root / "bench" / "traffic")
    with pytest.raises(ValueError, match=(
            r"no rule for parameter leaf 'groups/blocks/pos0/attn/wkv_a' "
            r"in model family 'mla_moe'")):
        harness.build(config, mix, SEED, chips=cell["chips"],
                      families=tiny_root / "bench" / "families")


def test_a_familys_sharded_counts_are_checked_against_the_mesh():
    family = harness.load_family("mla_moe", PLUGIN)
    config = json.loads((PLUGIN / "mla-moe-tiny.json").read_text())
    config["serve"]["mesh"] = {"data": 1, "model": 4}
    config["model"]["n_routed_experts"] = 6
    with pytest.raises(ValueError, match=r"6 routed experts do not divide "
                                         r"over the 4-way model axis"):
        harness.make_mesh(config, 4, family)


# -------------------------------------------------------- kernel tables
@pytest.mark.parametrize("entry", ["paged_flash_decode", paged_flash_decode],
                         ids=["by_name", "by_jitted_function"])
def test_a_family_kernel_entry_is_found_in_a_recorded_trace(entry):
    t = trace_mod.load_json(TESTDATA / "v5e_chat_decode.json.gz")
    family = types.SimpleNamespace(KERNELS={"walk": entry},
                                   PROGRAMS={"decode": "walk"})
    kernels, programs = trace_mod.tables(family)
    assert trace_mod.kernel_s(t, "walk", kernels) == \
        trace_mod.kernel_s(t, "page_walk") > 0
    assert trace_mod.program_s(t, "decode", kernels, programs) == \
        trace_mod.program_s(t, "decode")
    assert trace_mod.breakdown(t, kernels=kernels) == trace_mod.breakdown(t)


# --------------------------------------------------------------- counts
def traced_readings(family, shape):
    """Readings over a recorded v5e trace and one traced boundary that
    admitted a 100-token prompt and decoded three tokens from 10."""
    b = types.SimpleNamespace(decode=[(10, 3)], admitted=[100])
    gate = types.SimpleNamespace(boundaries=[b])
    tracer = types.SimpleNamespace(first=0, last=1, t_stop=1.0)
    return readings.Readings(
        gate=gate, seconds=1.0, shape=shape, n_slots=4, sync_interval=2,
        chips=1, kind="TPU v5 lite", tracer=tracer, family=family,
        trace=trace_mod.load_json(TESTDATA / "v5e_chat_decode.json.gz"))


def test_readings_take_a_familys_counts():
    family = harness.load_family("mla_moe", PLUGIN)
    shape = json.loads((PLUGIN / "mla-moe-tiny.json").read_text())["model"]
    r = traced_readings(family, shape)
    peak = peaks.peak_for("TPU v5 lite")
    admit_s = trace_mod.program_s(r.trace, "admit", r.kernels, r.programs)
    assert r.step_mfu_pct("admit") == pytest.approx(
        100 * family.prefill_flops(shape, 100) / (admit_s * peak.flops))
    f, n = family.flash(shape, 100)
    flash_s = trace_mod.kernel_s(r.trace, "flash")
    assert r.roofline_pct("mla_flash") == pytest.approx(
        100 * max(f / peak.flops, n / peak.hbm_bytes) / flash_s)
    # a token multiplies by its top-k routed experts and the shared ones,
    # not by every expert
    every = dict(shape, num_experts_per_tok=shape["n_routed_experts"])
    assert family.token_weights(shape) < family.token_weights(every)


def test_the_dense_family_reads_as_no_family():
    shape = json.loads(
        (REPO / "bench/configs/qwen2.5-3b.json").read_text())["model"]
    dense = traced_readings(harness.load_family("dense"), shape)
    plain = traced_readings(None, shape)
    for read in (lambda r: r.step_mfu_pct("decode"),
                 lambda r: r.step_mfu_pct("admit"),
                 lambda r: r.roofline_pct("page_walk"),
                 lambda r: r.roofline_pct("flash"), lambda r: r.breakdown()):
        assert read(dense) == read(plain) is not None
