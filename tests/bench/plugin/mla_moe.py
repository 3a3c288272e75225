"""MLA + MoE decoders (the DeepSeek-V2 layout) as a model family of the
benchmark, written only from what a family file may give: the program's
config for a configuration file, weight rules for the leaves the dense
rules do not know, work counts, a kernel name, and the gaps of served
tokens below a plain float32 reference.

``tests/bench/test_bench_family.py`` copies it into a checkout as
``bench/families/mla_moe.py``, beside a configuration that names it, and
serves a cell through ``bench/run.py`` without touching a file that was
there.

The reference, from the published architecture and sharing no code with
the program: pre-norm residual blocks. Attention is multi-head latent
attention: the query through a low-rank projection (``wq_a``, RMSNorm,
``wq_b``) or straight through ``wq_b``; keys and values from a compressed
latent (``wkv_a`` gives the latent and one rotary key shared by all
heads; RMSNorm of the latent, then ``wkv_b`` gives each head's key and
value parts); rotary embeddings (rotate-half) on the rotary parts only,
scores scaled by ``1/sqrt(qk_nope + qk_rope)``. The feed-forward is a
SwiGLU MLP in the first ``first_k_dense_replace`` layers and a mixture of
experts in the rest: softmax scores over the routed experts, the top
``num_experts_per_tok`` of them weighted by their scores (renormalised
where ``norm_topk_prob``), plus the shared experts as one SwiGLU of
``n_shared_experts`` times the expert width.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import flash_attention
from repro.models.config import ModelConfig

from bench import counts, reference

#: matrices the dense rules of ``bench/weights.py`` do not know, made as
#: those make theirs: bf16, variance 1/fan-in. The router is made alike
#: and stays float32, as the program routes in float32.
MATRICES = frozenset({"wq_a", "wq_b", "wkv_a", "wkv_b",
                      "we_gate", "we_up", "we_down"})


# ------------------------------------------------------------- program
def model_config(config: Dict) -> ModelConfig:
    """The program's config for a configuration file's ``model`` section
    (DeepSeek-V2's Hugging Face key names)."""
    m = config["model"]
    if not m.get("norm_topk_prob", False):
        raise ValueError("the program renormalises the top-k scores; a "
                         "configuration without norm_topk_prob differs")
    if float(m.get("routed_scaling_factor", 1.0)) != 1.0:
        raise ValueError("the program has no routed_scaling_factor")
    if m.get("rope_scaling"):
        raise ValueError("the program has plain rotary embeddings only")
    return ModelConfig(
        name=config["name"], family="moe",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        head_dim=m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=float(m["rope_theta"]),
        use_mla=True, q_lora_rank=m.get("q_lora_rank") or 0,
        kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"],
        v_head_dim=m["v_head_dim"], n_experts=m["n_routed_experts"],
        n_shared_experts=m["n_shared_experts"],
        top_k=m["num_experts_per_tok"],
        moe_d_ff=m["moe_intermediate_size"],
        first_dense=m["first_k_dense_replace"],
        tie_embeddings=bool(m["tie_word_embeddings"]),
        norm_eps=float(m["rms_norm_eps"]))


def weight_rule(path: Tuple[str, ...], shape, key) -> Optional[jax.Array]:
    """The latent projections, the experts and the router; every other
    leaf goes to the dense rules."""
    if path[-1] not in MATRICES | {"router"}:
        return None
    x = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])
    return x if path[-1] == "router" else x.astype(jnp.bfloat16)


def sharded_heads(config: Dict) -> Dict[str, int]:
    """The experts lie over the ``model`` axis of a mesh; attention and
    its latent are replicated, so no head count has to divide."""
    return {"routed experts": config["model"]["n_routed_experts"]}


# -------------------------------------------------------------- counts
def _dims(shape: Dict) -> Dict[str, int]:
    return {"d": shape["hidden_size"], "h": shape["num_attention_heads"],
            "nope": shape["qk_nope_head_dim"],
            "rope": shape["qk_rope_head_dim"], "v": shape["v_head_dim"],
            "lat": shape["kv_lora_rank"], "qr": shape.get("q_lora_rank") or 0,
            "layers": shape["num_hidden_layers"],
            "dense": shape["first_k_dense_replace"],
            "vocab": shape["vocab_size"]}


def token_weights(shape: Dict) -> int:
    """Weights one token multiplies by over all layers: the attention
    projections, and the dense MLP or the router, the top-k routed
    experts and the shared ones."""
    m = _dims(shape)
    qd = m["nope"] + m["rope"]
    q = (m["d"] * m["qr"] + m["qr"] * m["h"] * qd if m["qr"]
         else m["d"] * m["h"] * qd)
    attn = (q + m["d"] * (m["lat"] + m["rope"])
            + m["lat"] * m["h"] * (m["nope"] + m["v"])
            + m["h"] * m["v"] * m["d"])
    ff = shape["moe_intermediate_size"]
    moe = (m["d"] * shape["n_routed_experts"]
           + 3 * m["d"] * ff * (shape["num_experts_per_tok"]
                                + shape["n_shared_experts"]))
    dense = 3 * m["d"] * shape["intermediate_size"]
    return (attn * m["layers"] + dense * m["dense"]
            + moe * (m["layers"] - m["dense"]))


def decode_token_flops(shape: Dict, attended: int) -> float:
    """One decoded token: its weights, the absorbed attention over
    ``attended`` latent positions (scores against latent and rotary key,
    values from the latent) and the logits."""
    m = _dims(shape)
    attn = 2.0 * m["h"] * (2 * m["lat"] + m["rope"]) * attended * m["layers"]
    return 2.0 * token_weights(shape) + attn + 2.0 * m["d"] * m["vocab"]


def flash(shape: Dict, length: int) -> Tuple[float, float]:
    """(flops, bytes) of causal prefill attention over ``length`` true
    tokens, with keys of ``qk_nope + qk_rope`` and values of
    ``v_head_dim``: Q, K, V read and O written once."""
    m = _dims(shape)
    qd = m["nope"] + m["rope"]
    pairs = length * (length + 1) / 2.0
    flops = 2.0 * m["h"] * (qd + m["v"]) * pairs
    io = m["h"] * (2 * qd + 2 * m["v"]) * length * counts.BF16
    return flops * m["layers"], float(io) * m["layers"]


def prefill_flops(shape: Dict, length: int) -> float:
    m = _dims(shape)
    return (2.0 * token_weights(shape) * length + flash(shape, length)[0]
            + 2.0 * m["d"] * m["vocab"])


def _flash_work(shape: Dict, boundary) -> Tuple[float, float]:
    f = n = 0.0
    for length in boundary.admitted:
        df, dn = flash(shape, length)
        f, n = f + df, n + dn
    return f, n


#: the prefill's attention runs the program's flash kernel
KERNELS = {"mla_flash": flash_attention}
KERNEL_WORK = {"mla_flash": _flash_work}
PROGRAMS = {"admit": "mla_flash"}


# ----------------------------------------------------------- reference
def _mm(x, w, fp8):
    return reference._mm(x, w.astype(jnp.float32), fp8)


def _norm(x, p, eps):
    return reference._rmsnorm(x, p["scale"], eps)


def _swiglu(h, p, fp8):
    return _mm(jax.nn.silu(_mm(h, p["w_gate"], fp8)) * _mm(h, p["w_up"], fp8),
               p["w_down"], fp8)


def _attention(shape, fp8, h, a):
    s, eps, theta = h.shape[0], shape["rms_norm_eps"], shape["rope_theta"]
    nh, nope, rope = (shape["num_attention_heads"], shape["qk_nope_head_dim"],
                      shape["qk_rope_head_dim"])
    lat, vd = shape["kv_lora_rank"], shape["v_head_dim"]
    q_in = (_norm(_mm(h, a["wq_a"], fp8), a["q_norm"], eps) if "wq_a" in a
            else h)
    q = _mm(q_in, a["wq_b"], fp8).reshape(s, nh, nope + rope)
    q = q.transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope],
                         reference._rope(q[..., nope:], theta)], -1)
    kv_a = _mm(h, a["wkv_a"], fp8)
    k_rope = reference._rope(kv_a[None, :, lat:], theta)       # (1, S, r)
    kv = _mm(_norm(kv_a[:, :lat], a["kv_norm"], eps), a["wkv_b"], fp8)
    kv = kv.reshape(s, nh, nope + vd).transpose(1, 0, 2)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (nh, s, rope))], -1)
    scores = jnp.einsum("hqd,hkd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST)
    pos = jnp.arange(s)
    scores = jnp.where(pos[:, None] >= pos[None, :],
                       scores / np.sqrt(nope + rope), -jnp.inf)
    o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), kv[..., nope:],
                   precision=jax.lax.Precision.HIGHEST)
    return _mm(o.transpose(1, 0, 2).reshape(s, nh * vd), a["wo"], fp8)


def _experts(shape, fp8, h, p):
    """Routed experts, each run over every token and weighted by its gate
    (0 for the tokens that did not pick it), plus the shared experts."""
    probs = jax.nn.softmax(
        jnp.dot(h, p["router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST), -1)
    top, idx = jax.lax.top_k(probs, shape["num_experts_per_tok"])
    if shape["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)          # (S, E)

    def one(y, e):
        wg, wu, wd, g = e
        return y + g[:, None] * _swiglu(
            h, {"w_gate": wg, "w_up": wu, "w_down": wd}, fp8), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (p["we_gate"], p["we_up"], p["we_down"], gates.T))
    return y + _swiglu(h, p["shared"], fp8)


def _layer(shape, fp8, x, p):
    eps = shape["rms_norm_eps"]
    x = x + _attention(shape, fp8, _norm(x, p["ln1"], eps), p["attn"])
    h = _norm(x, p["ln2"], eps)
    return x + (_experts(shape, fp8, h, p["moe"]) if "moe" in p
                else _swiglu(h, p["mlp"], fp8))


@functools.partial(jax.jit, static_argnames=("shape_items", "fp8", "n_out"))
def _logits(params, tokens, start, *, shape_items, fp8, n_out):
    shape = dict(shape_items)
    x = params["tok"]["embed"][tokens].astype(jnp.float32)
    for stack in reference._layer_stacks(params):
        order = sorted(stack, key=lambda k: int(k[3:]))

        def repeat(c, p):
            for pos in order:
                c = _layer(shape, fp8, c, p[pos])
            return c, None

        x, _ = jax.lax.scan(repeat, x, stack)
    x = _norm(x, params["final_norm"], shape["rms_norm_eps"])
    rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    table = params["tok"].get("unembed", params["tok"]["embed"])
    return _mm(rows, table[:shape["vocab_size"]].T, fp8)


def token_gaps(shape: Dict, params: Dict, samples, *, n_out: int,
               control: bool = False, step: int = 1024):
    """As ``bench/reference.py``'s ``token_gaps``, through this family's
    forward pass."""
    items = tuple(sorted((k, v) for k, v in shape.items()
                         if isinstance(v, (int, float, bool, str))))
    served_g, control_g = [np.zeros((0,), np.float32)], []
    for prompt, served in samples:
        served = np.asarray(served, np.int32)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        start = len(prompt) - 1
        tokens = np.zeros((reference._bucket(max(len(seq), start + n_out),
                                             step),), np.int32)
        tokens[:len(seq)] = seq

        def logits(fp8):
            out = _logits(params, jnp.asarray(tokens),
                          jnp.asarray(start, jnp.int32), shape_items=items,
                          fp8=fp8, n_out=n_out)
            return np.asarray(out[:len(served)])

        with jax.default_matmul_precision("highest"):
            ref = logits(False)
            served_g.append(reference.gaps(ref, served))
            if control:
                control_g.append(reference.gaps(ref, logits(True).argmax(-1)))
    return (np.concatenate(served_g),
            np.concatenate(control_g) if control else None)
