"""Operations and bytes the work needs, from the true lengths of the
requests -- never from a kernel's grid or a padded bucket.

``shape`` is the model section of a configuration file (Hugging Face key
names). Every count is for the whole model on all chips; a caller that
divides by chips does so itself.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

BF16 = 2    # bytes of a served weight, activation or KV value


def dims(shape: Dict) -> Dict[str, int]:
    d = int(shape["hidden_size"])
    hq = int(shape["num_attention_heads"])
    return {
        "d": d, "hq": hq, "hkv": int(shape["num_key_value_heads"]),
        "hd": int(shape.get("head_dim") or d // hq),
        "ff": int(shape["intermediate_size"]),
        "layers": int(shape["num_hidden_layers"]),
        "vocab": int(shape["vocab_size"]),
    }


def layer_matmul_params(shape: Dict) -> int:
    """Weights one layer multiplies by: QKV, output projection, SwiGLU."""
    m = dims(shape)
    attn = m["d"] * m["hd"] * (m["hq"] + 2 * m["hkv"]) + m["hq"] * m["hd"] * m["d"]
    return attn + 3 * m["d"] * m["ff"]


def page_walk(shape: Dict, attended: int) -> Tuple[float, float]:
    """(flops, bytes) of decode attention for one token that attends over
    ``attended`` cached positions, summed over layers: QK and PV products,
    and the K and V of those positions read once, plus q in and out."""
    m = dims(shape)
    flops = 4.0 * m["hq"] * m["hd"] * attended
    kv = 2.0 * m["hkv"] * m["hd"] * attended * BF16
    qo = 2.0 * m["hq"] * m["hd"] * BF16
    return flops * m["layers"], (kv + qo) * m["layers"]


def flash(shape: Dict, length: int) -> Tuple[float, float]:
    """(flops, bytes) of causal prefill attention over a prompt of
    ``length`` true tokens, summed over layers: the L(L+1)/2 query-key
    pairs each head scores and weights, and Q, K, V read and O written
    once."""
    m = dims(shape)
    pairs = length * (length + 1) / 2.0
    flops = 4.0 * m["hq"] * m["hd"] * pairs
    io = (2 * m["hq"] + 2 * m["hkv"]) * m["hd"] * length * BF16
    return flops * m["layers"], float(io) * m["layers"]


def decode_token_flops(shape: Dict, attended: int) -> float:
    """Model flops of one decoded token: every weight once (2 flops per
    multiply-add), attention over ``attended`` positions, and the logits
    over the vocabulary."""
    m = dims(shape)
    weights = 2.0 * layer_matmul_params(shape) * m["layers"]
    logits = 2.0 * m["d"] * m["vocab"]
    return weights + page_walk(shape, attended)[0] + logits


def prefill_flops(shape: Dict, length: int) -> float:
    """Model flops of prefilling ``length`` true prompt tokens: every
    weight once per token, causal attention, and the logits of the last
    position only (the one the first token is read from)."""
    m = dims(shape)
    weights = 2.0 * layer_matmul_params(shape) * m["layers"] * length
    return weights + flash(shape, length)[0] + 2.0 * m["d"] * m["vocab"]


def decode_steps(spans: Iterable[Tuple[int, int]]) -> Iterable[int]:
    """Attended lengths of every decoded token, from ``(L0, g)`` pairs: a
    slot whose device frontier was ``L0`` when its chunk began and that
    produced ``g`` tokens attended over ``L0 + 1 .. L0 + g`` positions."""
    for l0, g in spans:
        for j in range(g):
            yield l0 + 1 + j


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes: float) -> Tuple[float, str]:
    """Least time the chip(s) could take over the time taken, in %, and
    which bound set the least time."""
    t_f, t_b = flops / peak_flops, nbytes / peak_bytes
    bound = "compute" if t_f >= t_b else "memory"
    return 100.0 * max(t_f, t_b) / seconds, bound
