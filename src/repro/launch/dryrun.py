import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real train_step / serve_step with
ShapeDtypeStruct inputs (no allocation), compiles it for the production mesh
(16x16 single-pod, 2x16x16 multi-pod) on 512 forced host devices, and records:

  * compiled.memory_analysis()  -> bytes/device (proves it fits a v5e chip)
  * compiled.cost_analysis()    -> per-device HLO FLOPs / bytes accessed
  * collective traffic          -> parsed from the partitioned HLO
                                   (all-gather/all-reduce/reduce-scatter/
                                   all-to-all/collective-permute), split into
                                   intra-pod vs pod-crossing by replica-group
                                   span

Artifacts land in benchmarks/artifacts/dryrun/<mesh>/<arch>/<shape>.json —
benchmarks/roofline.py turns them into EXPERIMENTS.md §Roofline.

NOTE: the XLA_FLAGS line above MUST precede any jax-importing import.
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.core import traffic
from repro.core.planner import RooflineReport, attention_plan
from repro.core.target import get_target, set_target
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.models.api import SHAPES
from repro.train import optimizer as opt_mod
from repro.train.loop import TrainConfig, make_train_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "../../../benchmarks/artifacts/dryrun")

#: per-arch knobs: memory fitting (microbatches, int8 moments) + the
#: planner/§Perf choices (layout, MoE capacity factor). "dp" layout = batch
#: spans the model axis, weights FSDP-gathered at use — measured wins on the
#: small/medium dense archs (EXPERIMENTS.md §Perf); MoE archs need the model
#: axis for EP and keep "tp".
TRAIN_OVERRIDES: Dict[str, Dict[str, Any]] = {
    "deepseek-v2-236b": dict(n_microbatches=16, quantized=True,
                             capacity_factor=1.25),
    "jamba-1.5-large-398b": dict(n_microbatches=8, quantized=True,
                                 capacity_factor=1.25),
    "gemma3-27b": dict(n_microbatches=8, quantized=False),
    "qwen3-moe-30b-a3b": dict(n_microbatches=8, quantized=False,
                              capacity_factor=1.25),
    "mistral-nemo-12b": dict(n_microbatches=8, quantized=False, layout="dp"),
    "yi-6b": dict(n_microbatches=8, quantized=False, layout="dp"),
    "qwen2.5-3b": dict(n_microbatches=8, quantized=False, layout="dp"),
    "qwen2-vl-2b": dict(n_microbatches=8, quantized=False, layout="dp"),
    "falcon-mamba-7b": dict(n_microbatches=8, quantized=False),
    "seamless-m4t-medium": dict(n_microbatches=4, quantized=False),
}


# -------------------------------------------------- flash traffic correction

def _visible_kv_elems(sq: int, skv: int, bq: int, bkv: int,
                      causal: bool, window: Optional[int]) -> int:
    """KV elements each Q block must stream, summed over Q blocks."""
    total = 0
    for i in range(-(-sq // bq)):
        hi = min(skv, (i + 1) * bq) if causal else skv
        lo = 0
        if window is not None:
            lo = max(0, i * bq - window)
        # round to block granularity (whole blocks are streamed)
        lo_b = (lo // bkv) * bkv
        hi_b = min(skv, -(-hi // bkv) * bkv)
        total += max(0, hi_b - lo_b)
    return total


def attn_traffic_correction(cfg, shape, cost_block: int) -> float:
    """Bytes to ADD to the measured cost-mode HBM traffic: the real Pallas
    plan uses smaller KV blocks (scores must fit VMEM), so KV re-reads exceed
    what the capped-trip cost lowering streamed. Exact block-count delta."""
    if shape.kind != "prefill" or cfg.n_heads == 0:
        return 0.0  # train_4k/decode lower the exact direct path
    sq = skv = shape.seq_len
    d = cfg.head_dim if not cfg.use_mla else (
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim) // 2
    plan = attention_plan(sq, skv, d)
    delta = 0.0
    for i in range(cfg.n_layers):
        kind = cfg.kind_for_layer(i)
        if kind.attn == "mamba":
            continue
        r_p = _visible_kv_elems(sq, skv, plan.block_q, plan.block_kv,
                                True, kind.window)
        r_c = _visible_kv_elems(sq, skv, cost_block, cost_block,
                                True, kind.window)
        hkv = max(cfg.n_kv_heads, 1)
        delta += shape.global_batch * hkv * 2 * d * 2 * (r_p - r_c)
    return max(delta, 0.0)


# ------------------------------------------------------------ input specs

def batch_shard_specs(batch: Any, mesh) -> Any:
    """Sharding for train/prefill batches: batch dim over (pod, data) —
    plus `model` under the DP-dominant layout."""
    axes = ("pod", "data", "model") if shd.layout() == "dp" \
        else ("pod", "data")
    dp = tuple(a for a in axes if a in mesh.axis_names)

    def spec(leaf):
        s = (dp,) + (None,) * (len(leaf.shape) - 1)
        return NamedSharding(mesh, shd.fix_spec_for(mesh, P(*s), leaf.shape))
    return jax.tree.map(spec, batch)


def decode_shard_specs(inputs: Any, mesh, *, batch: int) -> Any:
    """Decode-cell shardings: pooled KV (seq over `model`; batch over
    (pod,data) when it divides, else seq over everything)."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    seq_axes = ("model",) if batch > 1 else (dp + ("model",))

    def spec_for(path_names, leaf):
        name = path_names[-1] if path_names else ""
        r = len(leaf.shape)
        if name in ("k", "v"):            # (rep, B, H, S, D)
            s = (None, dp, None, seq_axes, None)[-r:]
        elif name in ("ckv", "krope"):    # (rep, B, S, lora)
            s = (None, dp, seq_axes, None)[-r:]
        elif name == "conv":              # (rep, B, K-1, Di)
            s = (None, dp, None, "model")[-r:]
        elif name == "ssm":               # (rep, B, Di, Ds)
            s = (None, dp, "model", None)[-r:]
        elif name == "enc_out":           # (B, S, d)
            s = (dp, None, None)
        elif name == "tokens":
            s = (dp, None)
        else:
            s = (None,) * r
        return NamedSharding(mesh, shd.fix_spec_for(mesh, P(*s), leaf.shape))

    flat, tdef = jax.tree_util.tree_flatten_with_path(inputs)
    out = []
    for path, leaf in flat:
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        out.append(spec_for(names, leaf))
    return jax.tree_util.tree_unflatten(tdef, out)


# ------------------------------------------------------- HLO collective scan

_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*((?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?,?\s*)+)"
    r"\s*(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "s16": 2, "u16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "c64": 8, "u4": 1, "s4": 1}

#: bytes-on-wire multiplier per collective kind (ring algorithms)
_WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shapes_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _crosses_pod(line: str, pod_stride: int = 256) -> bool:
    m = _GROUPS_RE.search(line)
    if m:
        ids = [int(x) for x in m.group(1).split(",")[:64]]
        return len(ids) > 1 and (max(ids) - min(ids)) >= pod_stride
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota groups [n_groups, group_size]<=[dims](T(perm)): the group walks
        # the minor dims of the (possibly transposed) device iota; it crosses
        # the pod iff the group's span covers the leading (pod) dim.
        n_groups, g_size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        perm = ([int(x) for x in m.group(4).split(",")]
                if m.group(4) else list(range(len(dims))))
        # stride of one step within a group in linear device id space
        permuted = [dims[i] for i in perm]
        # group dimension(s) are the trailing axes of the permuted iota
        span = 1
        trailing = 1
        for ax in reversed(range(len(permuted))):
            if trailing >= g_size:
                break
            trailing *= permuted[ax]
            # linear stride of this permuted axis in original id space
            orig_ax = perm[ax]
            stride = 1
            for j in range(orig_ax + 1, len(dims)):
                stride *= dims[j]
            span = max(span, stride * (min(trailing, g_size) - 1)
                       if permuted[ax] > 1 else span)
        return span >= pod_stride
    return False


def collect_collectives(hlo_text: str, multi_pod: bool,
                        top_k: int = 8) -> Dict[str, Any]:
    """Sum wire bytes of every collective in the partitioned HLO.

    bf16-promotion correction: the CPU backend cannot execute bf16 dots, so
    XLA:CPU re-promotes bf16 operands to f32 *after* our bf16 cast — the
    gathered weight shows as f32 with a ``convert_convert_fusion`` operand
    (master f32 -> bf16 cast -> CPU f32 promotion). On the TPU target the
    gather stays bf16, so such ops are counted at half their f32 bytes.
    Both raw and corrected sums are recorded.
    """
    intra = 0.0
    cross = 0.0
    raw = 0.0
    counts: Dict[str, int] = {}
    biggest = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(3)
        nbytes = _shape_bytes(m.group(2)) * _WIRE_FACTOR[kind]
        raw += nbytes
        # CPU f32-promotion signature: operand is a double convert
        tail = line[m.end():]
        if "f32[" in m.group(2) and "convert_convert" in tail.split(")")[0]:
            nbytes *= 0.5
        counts[kind] = counts.get(kind, 0) + 1
        biggest.append((nbytes, kind, m.group(2).strip()[:80]))
        if multi_pod and _crosses_pod(line):
            cross += nbytes
        else:
            intra += nbytes
    biggest.sort(reverse=True)
    return {"intra_bytes": intra, "cross_pod_bytes": cross,
            "raw_bytes_uncorrected": raw, "counts": counts,
            "top": [dict(bytes=b, kind=k, shape=s)
                    for b, k, s in biggest[:top_k]]}


# ---------------------------------------------------------------- dry run

def _serving_param_specs(params_s):
    """Serving stores weights in bf16 (the deploy format): cast the >=2-D
    f32 param specs, keeping the numerics-sensitive ones f32 (same exclusion
    list as the training-side compute cast)."""
    from repro.train.loop import _F32_PARAM_NAMES
    flat, tdef = jax.tree_util.tree_flatten_with_path(params_s)
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path).lower()
        if (len(leaf.shape) >= 2 and leaf.dtype == jnp.float32
                and not any(n in name for n in _F32_PARAM_NAMES)):
            leaf = jax.ShapeDtypeStruct(leaf.shape, jnp.bfloat16)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tdef, out)


def _lower_cell(model, shape, mesh, ov, *, n_micro_override=None):
    """Build + lower the cell's step function under the ambient mesh."""
    cfg = model.cfg
    if shape.kind == "train":
        tcfg = TrainConfig(
            opt=opt_mod.OptConfig(quantized_moments=ov.get("quantized", False)),
            n_microbatches=(n_micro_override if n_micro_override is not None
                            else ov.get("n_microbatches", 1)))
        step_fn = make_train_step(model, tcfg)
        params_s = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        opt_s = jax.eval_shape(lambda: opt_mod.init_opt_state(params_s, tcfg.opt))
        batch_s = model.input_specs(shape)
        jitted = jax.jit(step_fn,
                         in_shardings=(shd.named_shardings(params_s, mesh),
                                       shd.named_shardings(opt_s, mesh),
                                       batch_shard_specs(batch_s, mesh)),
                         donate_argnums=(0, 1))
        return jitted.lower(params_s, opt_s, batch_s)
    if shape.kind == "prefill":
        params_s = _serving_param_specs(
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
        batch_s = model.input_specs(shape)

        def prefill_fn(params, batch):
            return model.prefill(params, batch, shape.seq_len)

        jitted = jax.jit(prefill_fn,
                         in_shardings=(shd.named_shardings(params_s, mesh),
                                       batch_shard_specs(batch_s, mesh)))
        return jitted.lower(params_s, batch_s)
    inputs = model.input_specs(shape)
    params_s = _serving_param_specs(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
    i_shard = decode_shard_specs(inputs, mesh, batch=shape.global_batch)

    def serve_step(params, tokens, state, cache_len):
        return model.decode_step(params, tokens, state, cache_len)

    jitted = jax.jit(serve_step,
                     in_shardings=(shd.named_shardings(params_s, mesh),
                                   i_shard["tokens"], i_shard["state"],
                                   i_shard["cache_len"]),
                     donate_argnums=(2,))
    return jitted.lower(params_s, inputs["tokens"], inputs["state"],
                        inputs["cache_len"])


def _scaled_cfg(cfg, k: int):
    """Config with the scanned body at k repetitions (head/tail intact).
    Returns (cfg_k, full_reps). Quantities linear in body reps extrapolate
    exactly: Q(n) = Q(1) + (Q(2) - Q(1)) * (n - 1)."""
    import dataclasses as dc
    groups = cfg.layer_groups()
    body = next(g for g in groups if g.name == "blocks")
    period = len(body.pattern)
    extra = cfg.n_layers - body.n_layers
    repl = dict(n_layers=extra + k * period)
    if cfg.n_encoder_layers:
        repl["n_encoder_layers"] = k
    return dc.replace(cfg, **repl), body.n_repeat


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool,
                verbose: bool = True) -> Dict[str, Any]:
    try:
        return _dryrun_cell(arch, shape_name, multi_pod=multi_pod,
                            verbose=verbose)
    finally:
        os.environ.pop("REPRO_LAYOUT", None)


def _dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool,
                 verbose: bool = True) -> Dict[str, Any]:
    """Per cell:

    A. *memory* lowering — full depth, scans rolled (while-body buffers
       counted right), production microbatching: memory_analysis is the
       fits-on-chip proof; this is THE required lower().compile() pass.
    B. *cost* lowerings — REPRO_COST_MODE=1 (scans unrolled so
       HloCostAnalysis sees every body), at body-depth k=1 and k=2, then
       exact linear extrapolation to full depth (scan groups are homogeneous,
       so FLOPs and collective bytes are affine in body repetitions).
    The roofline memory term comes from the analytic TPU traffic model
    (core/traffic.py) — CPU-backend 'bytes accessed' is recorded but not
    used (CPU fusion overstates TPU HBM traffic by ~75x, see DESIGN.md).
    """
    cfg = get_config(arch)
    ov_pre = TRAIN_OVERRIDES.get(arch, {})
    if ov_pre.get("capacity_factor"):
        import dataclasses as _dc
        cfg = _dc.replace(cfg, capacity_factor=ov_pre["capacity_factor"])
    model = build_model(cfg)
    shape = SHAPES[shape_name]
    if shape_name not in model.runnable_shapes():
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "full-attention arch: no sub-quadratic 500k path"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    ov = dict(TRAIN_OVERRIDES.get(arch, {}))
    n_chips = 512 if multi_pod else 256
    mesh_dims = traffic.MeshDims(pod=2 if multi_pod else 1, data=16, model=16)
    # planner-chosen activation layout: train cells of small dense models run
    # DP-dominant (model axis joins DP; weights gathered at use). Only viable
    # when the global batch covers every device — otherwise the model columns
    # compute redundantly (e.g. batch 256 on the 512-chip multi-pod mesh)
    # and the cell stays TP.
    if (shape.kind == "train" and ov.get("layout") == "dp"
            and shape.global_batch % n_chips == 0):
        os.environ["REPRO_LAYOUT"] = "dp"
    # decode cells: weights resident (data-replicated dense, 2D experts with
    # token-gathering partial-K MoE) — gather-at-use would dwarf the tokens
    if shape.kind == "decode":
        os.environ["REPRO_LAYOUT"] = "infer"
    # microbatch rows must cover the whole DP extent, else the per-microbatch
    # batch dim cannot shard across it (2x16x16: n_micro <= 8; dp layout: 1)
    dp = mesh_dims.dp
    if os.environ.get("REPRO_LAYOUT") == "dp":
        dp *= mesh_dims.model
    if shape.kind == "train":
        max_micro = max(shape.global_batch // dp, 1)
        ov["n_microbatches"] = min(ov.get("n_microbatches", 1), max_micro)

    # --- A: memory lowering (full depth) ------------------------------------
    t0 = time.time()
    with jax.set_mesh(mesh):
        compiled_mem = _lower_cell(model, shape, mesh, ov).compile()
    t_mem = time.time() - t0
    mem = compiled_mem.memory_analysis()
    mem_rec = {k: getattr(mem, k, None) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes")}

    # --- B: cost lowerings at k=1,2 + exact extrapolation --------------------
    t0 = time.time()
    os.environ["REPRO_COST_MODE"] = "1"
    q = {}
    try:
        for k in (1, 2):
            cfg_k, full_reps = _scaled_cfg(cfg, k)
            model_k = build_model(cfg_k)
            with jax.set_mesh(mesh):
                compiled_k = _lower_cell(model_k, shape, mesh, ov,
                                         n_micro_override=1).compile()
            cost_k = compiled_k.cost_analysis()
            coll_k = collect_collectives(compiled_k.as_text(), multi_pod)
            q[k] = dict(flops=float(cost_k.get("flops", 0.0)),
                        bytes=float(cost_k.get("bytes accessed", 0.0)),
                        intra=coll_k["intra_bytes"],
                        cross=coll_k["cross_pod_bytes"],
                        counts=coll_k["counts"])
    finally:
        os.environ.pop("REPRO_COST_MODE", None)
    t_cost = time.time() - t0

    def extrap(key):
        return q[1][key] + (q[2][key] - q[1][key]) * (full_reps - 1)

    flops = extrap("flops")
    bytes_acc = extrap("bytes")
    intra = extrap("intra")
    cross = extrap("cross")

    # analytic corrections / terms
    n_micro = ov.get("n_microbatches", 1) if shape.kind == "train" else 1
    total_params, active_params = cfg.param_count()
    regather = ((n_micro - 1) * 2.0 * total_params / mesh_dims.model
                if n_micro > 1 else 0.0)
    hbm = traffic.step_traffic(cfg, kind=shape.kind, seq_len=shape.seq_len,
                               global_batch=shape.global_batch,
                               mesh=mesh_dims, n_micro=n_micro)
    resid = traffic.hbm_residency(cfg, kind=shape.kind, seq_len=shape.seq_len,
                                  global_batch=shape.global_batch,
                                  mesh=mesh_dims,
                                  quantized_moments=ov.get("quantized", False))

    tokens = (shape.global_batch * shape.seq_len
              if shape.kind in ("train", "prefill") else shape.global_batch)
    if shape.kind == "train":
        model_flops = cfg.model_flops(tokens)
    else:
        model_flops = 2.0 * active_params * tokens

    target = get_target()
    assert target.kind == "tpu", f"dry-run needs a TPU target, got {target.name}"
    report = RooflineReport(
        name=f"{arch}/{shape_name}", n_chips=n_chips,
        hlo_flops=flops * n_chips,          # cost_analysis is per-device
        hlo_bytes=hbm["total"] * n_chips,   # analytic TPU traffic model
        collective_bytes=(intra + regather) * n_chips,
        pod_collective_bytes=cross * n_chips,
        model_flops=model_flops, profile=target.profile)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "target": target.name,
        "status": "ok",
        "compile_mem_s": round(t_mem, 1), "compile_cost_s": round(t_cost, 1),
        "n_microbatches": n_micro,
        "memory": mem_rec,
        "residency_model": resid,
        "cost": {"flops_per_device": flops,
                 "xla_cpu_bytes_per_device": bytes_acc,
                 "traffic_model_bytes_per_device": hbm,
                 "micro_regather_per_device": regather},
        "collectives": {"intra_bytes": intra, "cross_pod_bytes": cross,
                        "counts": q[2]["counts"]},
        "roofline": report.to_dict(),
    }
    if verbose:
        tmp = mem_rec.get("temp_size_in_bytes") or 0
        arg = mem_rec.get("argument_size_in_bytes") or 0
        print(f"[{rec['mesh']}] {arch}/{shape_name}: "
              f"args {arg/2**30:.2f} + temp {tmp/2**30:.2f} GiB/dev, "
              f"{flops/1e9:.1f} GF/dev, useful={report.useful_flops_ratio:.2f}, "
              f"bound={report.bound}, roofline={report.roofline_fraction:.2f}, "
              f"compile {t_mem:.0f}+{t_cost:.0f}s", flush=True)
    return rec


def artifact_path(mesh_tag: str, arch: str, shape: str) -> str:
    d = os.path.abspath(os.path.join(ARTIFACT_DIR, mesh_tag, arch))
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{shape}.json")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--target", default=None,
                    help="hardware target name from the registry "
                         "(default: current target, e.g. tpu-v5e)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    if args.target:
        set_target(args.target)

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for multi in meshes:
        tag = "2x16x16" if multi else "16x16"
        for arch in archs:
            for shape in shapes:
                path = artifact_path(tag, arch, shape)
                if os.path.exists(path) and not args.force:
                    continue
                try:
                    rec = dryrun_cell(arch, shape, multi_pod=multi)
                except Exception as e:  # record failures as artifacts too
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "mesh": tag,
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
