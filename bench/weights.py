"""Seeded weights, made by the benchmark and handed to the program, and
the same values again for the plain reference.  What is shared by every
architecture: the draw of one tensor and the key it is drawn from; each
``arch/<model_type>.py`` lists its own tensors and lays them out.

Every tensor is drawn from ``fold_in(fold_in(key(seed), tensor), layer)``,
so the program's stacked pytree (one jitted call, on the device, in the
dtype it is served in) and the reference's one-layer-at-a-time float32
copy hold the same numbers: the reference upcasts the served values.

Scales: matrices N(0, 1/fan_in) keep activations O(1) through every
layer; the embedding is N(0, 1), the same scale as what each random
layer adds to the residual stream, so the token's own embedding is not
drowned by the first layer's output and the logits depend on the
tokens (a trained model's residual stream is likewise far above its
norm's epsilon); norm scales are 1 + N(0, 0.05^2) so that a norm applied
with the wrong weights shows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NORM, EMBED, MATRIX = "norm", "embed", "matrix"


def draw(key, tensor: int, layer, shape, dtype, kind: str = MATRIX):
    """The ``tensor``-th tensor of an architecture's list at ``layer``, as
    served: a ``NORM`` scale in float32, an ``EMBED`` table or a
    ``MATRIX`` (fan-in first) in ``dtype``."""
    k = jax.random.fold_in(jax.random.fold_in(key, tensor), layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == NORM:
        return 1.0 + 0.05 * z
    if kind == EMBED:
        return z.astype(dtype)
    return (z / jnp.sqrt(jnp.float32(shape[0]))).astype(dtype)


def seed_key(seed: int):
    # --seed may exceed 32 bits: fold both halves into the key
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def check_layout(params, model):
    """Stop where ``params`` is not ``model.init``'s layout, leaf by leaf:
    the program's parameter layout changed under the benchmark."""
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit("the program's parameter layout changed: "
                         f"{jax.tree.map(lambda a: a.shape, want)}")
