"""Dense GQA decoders (Qwen2, Mistral): the program's config for a
configuration file, and the gaps of served tokens below the plain float32
reference (``bench/reference.py``).

A family file gives ``model_config(config)`` and ``token_gaps(shape,
params, samples, *, n_out, control)``; the harness finds it by the
configuration's ``family`` (``bench/families/<family>.py``). It may also
give, and the harness then takes:

* ``weight_rule(path, shape, key)``: a leaf's value in its served type,
  or ``None`` to leave it to the dense rules of ``bench/weights.py``;
* ``decode_token_flops(shape, attended)`` and ``prefill_flops(shape,
  length)``: the model flops of one decoded token and of one prefilled
  prompt, for the step MFUs (else ``bench/counts.py``'s);
* ``KERNEL_WORK``: kernel name -> ``fn(shape, boundary)``, the (flops,
  bytes) one traced boundary needs in that kernel at its true lengths;
* ``KERNELS`` (kernel name -> the jitted function that calls it, or its
  name) and ``PROGRAMS`` (``"decode"`` / ``"admit"`` -> the kernel that
  marks that program), laid over ``bench/trace.py``'s own tables;
* ``sharded_heads(config)``: what has to divide over a mesh's ``model``
  axis, by name (else the query and KV heads).

The dense family gives none of these: its weights, counts and kernel
names are the harness's own.
"""

from __future__ import annotations

from typing import Dict

from repro.models.config import ModelConfig

from bench import reference


def model_config(config: Dict) -> ModelConfig:
    """The program's config for a configuration file's ``model`` section
    (Hugging Face key names); ``assumed`` gives what the published config
    leaves out."""
    m, assumed = config["model"], config["assumed"]
    if m.get("hidden_act", "silu") != "silu":
        raise ValueError(f"unsupported activation {m['hidden_act']!r}")
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim") or assumed["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=float(m["rope_theta"]),
        qkv_bias=bool(assumed["qkv_bias"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        norm_eps=float(m["rms_norm_eps"]))


token_gaps = reference.token_gaps
