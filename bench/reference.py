"""The plain reference: a float32 forward pass of the dense GQA decoder
family (Qwen2 / Mistral), written from the published architecture and
sharing no code with the program.

Pre-norm residual blocks: RMSNorm, attention with grouped KV heads, an
optional bias on Q, K and V, rotary embeddings (rotate-half convention,
base ``rope_theta``) and a causal softmax; RMSNorm, then a SwiGLU MLP
(``down(silu(gate(x)) * up(x))``). A final RMSNorm, then logits against
the output table, which is the input embedding when the head is tied.

The weights come in as a tree the benchmark made from the seed
(``bench/weights.py``): ``tok.embed`` (and ``tok.unembed`` when untied),
``final_norm.scale``, and per layer group the layers stacked on axis 0
under ``ln1.scale``, ``attn.{wq,wk,wv,wo[,bq,bk,bv]}``, ``ln2.scale``,
``mlp.{w_gate,w_up,w_down}``; matrices are ``(in, out)``. Every value is
taken to float32 and every product runs at ``HIGHEST`` precision.

``fp8=True`` is the lower-precision control: the inputs of every matrix
product (weights per tensor, activations per row) are rounded to
float8 e4m3 with an absmax scale, the step a later change might be
tempted to take.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0      # largest finite float8_e4m3fn


def _fp8(x: jax.Array, axis) -> jax.Array:
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    """``x @ w`` in float32 at HIGHEST precision; ``fp8`` rounds both
    inputs first."""
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (H, S, D); rotate-half rotary embedding at positions 0..S-1."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(shape: Dict, fp8: bool, x: jax.Array, p: Dict) -> jax.Array:
    """One decoder layer over x: (S, d) float32."""
    f32 = lambda a: a.astype(jnp.float32)
    hq, hkv = shape["num_attention_heads"], shape["num_key_value_heads"]
    d = shape["hidden_size"]
    hd = shape.get("head_dim") or d // hq
    eps, theta = shape["rms_norm_eps"], shape["rope_theta"]
    s = x.shape[0]
    a = p["attn"]
    h = _rmsnorm(x, p["ln1"]["scale"], eps)
    q, k, v = (_mm(h, f32(a[w]), fp8) for w in ("wq", "wk", "wv"))
    if "bq" in a:
        q, k, v = q + f32(a["bq"]), k + f32(a["bk"]), v + f32(a["bv"])
    q = _rope(q.reshape(s, hq, hd).transpose(1, 0, 2), theta)
    k = _rope(k.reshape(s, hkv, hd).transpose(1, 0, 2), theta)
    v = v.reshape(s, hkv, hd).transpose(1, 0, 2)
    group = hq // hkv
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    scores = jnp.einsum("hqd,hkd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    pos = jnp.arange(s)
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores,
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", probs, v,
                   precision=jax.lax.Precision.HIGHEST)
    o = o.transpose(1, 0, 2).reshape(s, hq * hd)
    x = x + _mm(o, f32(a["wo"]), fp8)
    m = p["mlp"]
    h = _rmsnorm(x, p["ln2"]["scale"], eps)
    g = _mm(h, f32(m["w_gate"]), fp8)
    u = _mm(h, f32(m["w_up"]), fp8)
    return x + _mm(jax.nn.silu(g) * u, f32(m["w_down"]), fp8)


def _layer_stacks(params: Dict):
    """The stacked layer groups in stack order (a leading group, the
    repeated body, a trailing group), each as ``{pos: stacked params}``."""
    groups = params["groups"]
    order = [g for g in ("head", "blocks", "tail") if g in groups]
    if set(order) != set(groups):
        raise ValueError(f"unknown layer groups {sorted(groups)}")
    return [groups[g] for g in order]


@functools.partial(jax.jit, static_argnames=("shape_items", "fp8", "n_out"))
def _logits(params, tokens, start, *, shape_items, fp8, n_out):
    shape = dict(shape_items)
    x = params["tok"]["embed"][tokens].astype(jnp.float32)
    for stack in _layer_stacks(params):
        for pos in sorted(stack, key=lambda k: int(k[3:])):
            x, _ = jax.lax.scan(
                lambda c, p: (_layer(shape, fp8, c, p), None), x, stack[pos])
    x = _rmsnorm(x, params["final_norm"]["scale"], shape["rms_norm_eps"])
    rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    table = params["tok"].get("unembed", params["tok"]["embed"])
    table = table[:shape["vocab_size"]].astype(jnp.float32)
    if fp8:
        rows, table = _fp8(rows, -1), _fp8(table, None)
    return jnp.dot(rows, table.T, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _bucket(n: int, step: int) -> int:
    return -(-n // step) * step


def served_logits(shape: Dict, params: Dict, prompt: np.ndarray,
                  served: np.ndarray, *, n_out: int, fp8: bool = False,
                  step: int = 1024) -> np.ndarray:
    """Logits ``(len(served), vocab)`` at the positions that predict each
    served token: the prompt's last position, then each served token but
    the last, fed in as the program served them (teacher forcing).

    The sequence is right-padded to a multiple of ``step`` (causal, so the
    pad changes no earlier position) and ``n_out`` rows are taken, so one
    compiled program serves every request of a mix."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    start = len(prompt) - 1
    total = _bucket(max(len(seq), start + n_out), step)
    tokens = np.zeros((total,), np.int32)
    tokens[:len(seq)] = seq
    items = tuple(sorted((k, v) for k, v in shape.items()
                         if isinstance(v, (int, float, bool, str))))
    out = _logits(params, jnp.asarray(tokens), jnp.asarray(start, jnp.int32),
                  shape_items=items, fp8=fp8, n_out=n_out)
    return np.asarray(out[:len(served)])


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each token's logit lies
    (0 where the token is the reference's own argmax)."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]


def token_gaps(shape: Dict, params: Dict, samples, *, n_out: int,
               control: bool = False, step: int = 1024
               ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Over ``samples`` (``(prompt, served)`` pairs): the gap of every
    served token, and -- with ``control`` -- the gap of the token the fp8
    control puts first at the same positions (else ``None``)."""
    served_g, control_g = [np.zeros((0,), np.float32)], []
    for prompt, served in samples:
        served = np.asarray(served, np.int32)
        with jax.default_matmul_precision("highest"):
            ref = served_logits(shape, params, prompt, served, n_out=n_out,
                                step=step)
            served_g.append(gaps(ref, served))
            if control:
                low = served_logits(shape, params, prompt, served,
                                    n_out=n_out, fp8=True, step=step)
                control_g.append(gaps(ref, low.argmax(axis=-1)))
    return (np.concatenate(served_g),
            np.concatenate(control_g) if control else None)


def summary(g: np.ndarray) -> Dict[str, float]:
    """The widest and mean gap of a run's tokens, and the share of them
    that are not the reference's own argmax."""
    if not len(g):
        return {"widest": 0.0, "mean": 0.0, "off_argmax": 0.0}
    return {"widest": float(g.max()), "mean": float(g.mean()),
            "off_argmax": float((g > 0).mean())}
