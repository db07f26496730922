"""The Llama architecture (``model_type`` "llama"; Yi, arXiv:2403.04652,
keeps it): what the benchmark needs of it, found by ``bench/spec.py``'s
``architecture`` for a configuration file whose ``model_type`` names it.

- ``program_config``: the program's ``ModelConfig`` for the file;
- ``program_params``: the seeded weights in the program's layout;
- ``hidden`` and ``final``: the plain float32 reference forward and the
  final norm and LM head it ends in (``bench/reference.py``'s ``gaps``);
- ``decode_token_flops``, ``prefill_flops``, ``fused_decode_calls``,
  ``prefill_attn_calls``: the counts the per-layer readers take
  (``bench/flops.py``);
- ``tiny``: the file shrunk to a size the CPU tests run in seconds.

The reference is written from the published description (Hugging Face
``LlamaForCausalLM`` with the configuration file's keys), not from the
program: token embedding; per layer a pre-norm RMSNorm
(``rms_norm_eps``), grouped-query attention with rotary embeddings
(rotate-half, ``rope_theta``) and a causal softmax, a residual add, a
second RMSNorm, the SwiGLU MLP ``down(silu(gate(x)) * up(x))`` and a
residual add; a final RMSNorm and the untied LM head.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from bench import flops as F
from bench import reference as R
from bench import weights as W

# published (Hugging Face ``config.json``) keys -> the program's ModelConfig
HF_TO_PROGRAM = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
}

LAYER_TENSORS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                 "w_up", "w_down")
GLOBAL_TENSORS = ("embed", "final_norm", "head")
_ID = {n: i for i, n in enumerate(LAYER_TENSORS + GLOBAL_TENSORS)}
NORMS = ("attn_norm", "mlp_norm", "final_norm")

# widths the CPU tests run at (``tiny``)
TINY = dict(hidden_size=64, intermediate_size=128, vocab_size=256,
            num_attention_heads=4, num_key_value_heads=2,
            num_hidden_layers=2)


def program_config(conf):
    """The program's ``ModelConfig`` for a configuration file: the
    registry entry named by ``registry``, with every published size of the
    file written over it (so the file, not the registry, is what runs)."""
    from repro.configs import REGISTRY
    base = REGISTRY[conf["registry"]]
    sizes = {HF_TO_PROGRAM[k]: conf[k] for k in HF_TO_PROGRAM}
    dtype = conf["torch_dtype"]
    return dataclasses.replace(base, name=conf["name"], dtype=dtype,
                               head_dim=sizes["d_model"] // sizes["num_heads"],
                               param_dtype=dtype,
                               tie_embeddings=conf["tie_word_embeddings"],
                               **sizes)


def tiny(conf):
    return dict(conf, **TINY)


def shapes(conf):
    """Shape of each tensor, from a configuration file's published keys."""
    d, f, v = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    hd = d // conf["num_attention_heads"]
    q, kv = conf["num_attention_heads"] * hd, conf["num_key_value_heads"] * hd
    return {"attn_norm": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
            "wo": (q, d), "mlp_norm": (d,), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d), "embed": (v, d), "final_norm": (d,),
            "head": (d, v)}


def _draw(key, name, layer, shape, dtype):
    kind = (W.NORM if name in NORMS else W.EMBED if name == "embed"
            else W.MATRIX)
    return W.draw(key, _ID[name], layer, shape, dtype, kind)


@partial(jax.jit, static_argnums=(1, 2))
def _program_params(seed_key, conf_items, dtype_name):
    conf = dict(conf_items)
    dt = jnp.dtype(dtype_name)
    sh = shapes(conf)
    layers = jnp.arange(conf["num_hidden_layers"])

    def stacked(name):
        return jax.vmap(lambda i: _draw(seed_key, name, i, sh[name], dt))(
            layers)

    return {
        "embed": {"table": _draw(seed_key, "embed", 0, sh["embed"], dt)},
        "stack": {"b0": {
            "norm1": {"scale": stacked("attn_norm")},
            "mixer": {n: stacked(n) for n in ("wq", "wk", "wv", "wo")},
            "norm2": {"scale": stacked("mlp_norm")},
            "ffn": {"wg": stacked("w_gate"), "wi": stacked("w_up"),
                    "wo": stacked("w_down")},
        }},
        "final_norm": {"scale": _draw(seed_key, "final_norm", 0,
                                      sh["final_norm"], dt)},
        "head": {"w": _draw(seed_key, "head", 0, sh["head"], dt)},
    }


def _conf_items(conf):
    keys = ("hidden_size", "intermediate_size", "vocab_size",
            "num_attention_heads", "num_key_value_heads", "num_hidden_layers")
    return tuple((k, conf[k]) for k in keys)


def program_params(conf, seed: int, model=None):
    """The program's parameter pytree (``Model.init`` layout: one stacked
    group per layer), in one jitted call on the default device.  Where
    ``model`` is given, its ``init`` layout is checked leaf by leaf."""
    params = _program_params(W.seed_key(seed), _conf_items(conf),
                             conf["torch_dtype"])
    if model is not None:
        W.check_layout(params, model)
    return params


@partial(jax.jit, static_argnums=(1, 2))
def _layer_f32(seed_key, conf_items, dtype_name, layer):
    conf = dict(conf_items)
    sh = shapes(conf)
    dt = jnp.dtype(dtype_name)
    return {n: _draw(seed_key, n, layer, sh[n], dt).astype(jnp.float32)
            for n in LAYER_TENSORS}


def layer_f32(conf, seed: int, layer: int):
    """Layer ``layer``'s tensors as served, upcast to float32."""
    return _layer_f32(W.seed_key(seed), _conf_items(conf),
                      conf["torch_dtype"], jnp.int32(layer))


@partial(jax.jit, static_argnums=(1, 2, 3))
def _global_f32(seed_key, name, conf_items, dtype_name):
    sh = shapes(dict(conf_items))
    return _draw(seed_key, name, 0, sh[name], jnp.dtype(dtype_name)).astype(
        jnp.float32)


def global_f32(conf, seed: int, name: str):
    """``embed``, ``final_norm`` or ``head`` as served, upcast to float32."""
    return _global_f32(W.seed_key(seed), name, _conf_items(conf),
                       conf["torch_dtype"])


@partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, hyper, fp8):
    heads, kv_heads, eps, theta = hyper
    b, s, d = x.shape
    hd = d // heads
    h = R.rms(x, w["attn_norm"], eps)
    q = R.rope(R.mm(h, w["wq"], fp8).reshape(b, s, heads, hd), theta)
    k = R.rope(R.mm(h, w["wk"], fp8).reshape(b, s, kv_heads, hd), theta)
    v = R.mm(h, w["wv"], fp8).reshape(b, s, kv_heads, hd)
    x = x + R.mm(R.attention(q, k, v), w["wo"], fp8)
    h = R.rms(x, w["mlp_norm"], eps)
    return x + R.mm(jax.nn.silu(R.mm(h, w["w_gate"], fp8))
                    * R.mm(h, w["w_up"], fp8), w["w_down"], fp8)


def hidden(conf, seed: int, rows, fp8: bool = False):
    """Final hidden states (before the last norm) of each row of token ids,
    one row at a time (``reference.embedded``'s padding)."""
    hyper = (conf["num_attention_heads"], conf["num_key_value_heads"],
             float(conf["rms_norm_eps"]), float(conf["rope_theta"]))
    emb = global_f32(conf, seed, "embed")
    xs = R.embedded(emb, rows)
    del emb
    for i in range(conf["num_hidden_layers"]):
        w = layer_f32(conf, seed, i)
        xs = [_layer(x, w, hyper, fp8) for x in xs]
    return [x[0] for x in xs]


def final(conf, seed: int):
    """(final RMSNorm scale, LM head, epsilon) that ``hidden`` ends in."""
    return (global_f32(conf, seed, "final_norm"),
            global_f32(conf, seed, "head"), float(conf["rms_norm_eps"]))


def decode_token_flops(conf, pos: int) -> int:
    return F.decode_token_flops(F.Dims.of(conf), pos)


def prefill_flops(conf, length: int, offset: int) -> int:
    return F.prefill_flops(F.Dims.of(conf), length, offset)


def fused_decode_calls(conf, positions):
    """[(FLOPs, bytes, calls)] of one decode step's fused paged decode
    calls over the active slots at ``positions``: every layer makes one,
    all alike."""
    m = F.Dims.of(conf)
    return [(*F.fused_decode_call(m, positions), m.layers)]


def prefill_attn_calls(conf, length: int, offset: int):
    """[(FLOPs, bytes, calls)] of one prefill's paged attention calls:
    every layer makes one, all alike."""
    m = F.Dims.of(conf)
    return [(*F.prefill_attn_call(m, length, offset), m.layers)]
