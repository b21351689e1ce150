"""Random weights from the seed, made alike for the program and the reference.

Every weight is drawn leaf by leaf and layer by layer from a key folded out
of the run's seed, the leaf's path in the program's parameter tree
(``groups/p0/mixer/wq/w``) and the layer.  The program's tree is filled in
one jitted call on the device, in the dtype it serves in; the reference
draws the same leaf of the same layer again, rounds it to that dtype and
computes in float32.  So the reference takes no array the program made,
only the seed and the names.

Scales follow the usual initialisation of a pre-norm decoder: embeddings
N(0, 0.02), a linear ``d_in -> d_out`` N(0, d_in^-1/2), the output
projections of attention and MLP (``wo``, ``wd``) further divided by
sqrt(layers), norm gains 1 and biases 0.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

RESIDUAL_OUT = ("wo", "wd")


def seed_key(seed: int) -> jax.Array:
    """A threefry key from a seed of up to 64 bits."""
    words = jnp.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                      jnp.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _path_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key, path: str, shape, n_layers: int):
    """float32 values of one leaf (of one layer, for a stacked leaf)."""
    parts = path.split("/")
    name = parts[-1]
    if name == "g":
        return jnp.ones(shape, jnp.float32)
    if name == "b":
        return jnp.zeros(shape, jnp.float32)
    if name == "e":
        std = 0.02
    elif name == "w":
        std = shape[-2] ** -0.5
        if len(parts) >= 2 and parts[-2] in RESIDUAL_OUT:
            std /= max(1, n_layers) ** 0.5
    else:
        raise ValueError(f"no initialisation rule for leaf {path!r}")
    return jax.random.normal(key, shape, jnp.float32) * std


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def program_params(model, seed: int, dtype) -> dict:
    """The program's parameter tree, filled from the seed in one jitted call.

    Leaves under ``groups/`` are stacked over layers on axis 0; layer ``l``
    of such a leaf is drawn from the key folded with ``l``."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n_layers = model.cfg.n_layers

    def build(key):
        def leaf(path, sds):
            p = _path_str(path)
            k = _path_key(key, p)
            if p.startswith("groups/"):
                vals = jax.vmap(lambda l: _draw(jax.random.fold_in(k, l), p,
                                                sds.shape[1:], n_layers))(
                    jnp.arange(sds.shape[0]))
            else:
                vals = _draw(k, p, sds.shape, n_layers)
            return vals.astype(dtype)
        return jax.tree_util.tree_map_with_path(leaf, shapes)

    return jax.jit(build)(seed_key(seed))


def reference_leaf(seed: int, path: str, shape, n_layers: int, dtype,
                   layer=None) -> jax.Array:
    """One leaf as the program holds it (rounded to ``dtype``), in float32;
    ``layer`` picks one layer of a stacked leaf, ``shape`` excludes it."""
    k = _path_key(seed_key(seed), path)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return _draw(k, path, tuple(shape), n_layers).astype(dtype).astype(
        jnp.float32)
