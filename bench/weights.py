"""Seeded weights of a Llama-architecture (Yi) model, made by the
benchmark and handed to the program, and the same values again for the
plain reference.

Every tensor is drawn from ``fold_in(fold_in(key(seed), tensor), layer)``,
so the program's stacked pytree (one jitted call, on the device, in the
dtype it is served in) and the reference's one-layer-at-a-time float32
copy hold the same numbers: the reference upcasts the served bf16 values.

Scales: matrices N(0, 1/fan_in) keep activations O(1) through every
layer; the embedding is N(0, 1), the same scale as what each random
layer adds to the residual stream, so the token's own embedding is not
drowned by the first layer's output and the logits depend on the
tokens (a trained model's residual stream is likewise far above its
norm's epsilon); norm scales are 1 + N(0, 0.05^2) so that a norm applied
with the wrong weights shows.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

LAYER_TENSORS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                 "w_up", "w_down")
GLOBAL_TENSORS = ("embed", "final_norm", "head")
_ID = {n: i for i, n in enumerate(LAYER_TENSORS + GLOBAL_TENSORS)}
NORMS = ("attn_norm", "mlp_norm", "final_norm")


def shapes(conf):
    """Shape of each tensor, from a configuration file's published keys."""
    d, f, v = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    hd = d // conf["num_attention_heads"]
    q, kv = conf["num_attention_heads"] * hd, conf["num_key_value_heads"] * hd
    return {"attn_norm": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
            "wo": (q, d), "mlp_norm": (d,), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d), "embed": (v, d), "final_norm": (d,),
            "head": (d, v)}


def draw(key, name, layer, shape, dtype):
    """One tensor as served: norm scales in float32, the rest in ``dtype``."""
    k = jax.random.fold_in(jax.random.fold_in(key, _ID[name]), layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if name in NORMS:
        return 1.0 + 0.05 * z
    if name == "embed":
        return z.astype(dtype)
    return (z / jnp.sqrt(jnp.float32(shape[0]))).astype(dtype)


def _key(seed: int):
    # --seed may exceed 32 bits: fold both halves into the key
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


@partial(jax.jit, static_argnums=(1, 2))
def _program_params(seed_key, conf_items, dtype_name):
    conf = dict(conf_items)
    dt = jnp.dtype(dtype_name)
    sh = shapes(conf)
    layers = jnp.arange(conf["num_hidden_layers"])

    def stacked(name):
        return jax.vmap(lambda i: draw(seed_key, name, i, sh[name], dt))(
            layers)

    return {
        "embed": {"table": draw(seed_key, "embed", 0, sh["embed"], dt)},
        "stack": {"b0": {
            "norm1": {"scale": stacked("attn_norm")},
            "mixer": {n: stacked(n) for n in ("wq", "wk", "wv", "wo")},
            "norm2": {"scale": stacked("mlp_norm")},
            "ffn": {"wg": stacked("w_gate"), "wi": stacked("w_up"),
                    "wo": stacked("w_down")},
        }},
        "final_norm": {"scale": draw(seed_key, "final_norm", 0,
                                     sh["final_norm"], dt)},
        "head": {"w": draw(seed_key, "head", 0, sh["head"], dt)},
    }


def _conf_items(conf):
    keys = ("hidden_size", "intermediate_size", "vocab_size",
            "num_attention_heads", "num_key_value_heads", "num_hidden_layers")
    return tuple((k, conf[k]) for k in keys)


def program_params(conf, seed: int, model=None):
    """The program's parameter pytree (``Model.init`` layout: one stacked
    group per layer), in one jitted call on the default device.  Where
    ``model`` is given, its ``init`` layout is checked leaf by leaf."""
    params = _program_params(_key(seed), _conf_items(conf),
                             conf["torch_dtype"])
    if model is not None:
        want = jax.eval_shape(model.init, jax.random.key(0))
        got = jax.eval_shape(lambda: params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise SystemExit("the program's parameter layout changed: "
                             f"{jax.tree.map(lambda a: a.shape, want)}")
    return params


@partial(jax.jit, static_argnums=(1, 2))
def _layer_f32(seed_key, conf_items, dtype_name, layer):
    conf = dict(conf_items)
    sh = shapes(conf)
    dt = jnp.dtype(dtype_name)
    return {n: draw(seed_key, n, layer, sh[n], dt).astype(jnp.float32)
            for n in LAYER_TENSORS}


def layer_f32(conf, seed: int, layer: int):
    """Layer ``layer``'s tensors as served, upcast to float32."""
    return _layer_f32(_key(seed), _conf_items(conf), conf["torch_dtype"],
                      jnp.int32(layer))


@partial(jax.jit, static_argnums=(1, 2, 3))
def _global_f32(seed_key, name, conf_items, dtype_name):
    sh = shapes(dict(conf_items))
    return draw(seed_key, name, 0, sh[name], jnp.dtype(dtype_name)).astype(
        jnp.float32)


def global_f32(conf, seed: int, name: str):
    """``embed``, ``final_norm`` or ``head`` as served, upcast to float32."""
    return _global_f32(_key(seed), name, _conf_items(conf),
                       conf["torch_dtype"])
