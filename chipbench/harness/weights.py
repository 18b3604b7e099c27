"""Random weights from the seed, made by the benchmark and not the program.

The weights are the checkpoint the program is deployed from, in the tree its
dense decoder loads (stacked layers), and the reference regenerates any one
layer of them from the same seed on its own.  Every leaf draws from a key of
its own, and each layer of a stacked leaf from a key of its own, so a layer
made alone equals that layer of the whole tree, bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness.ops import dims

# (path, stacked over layers, shape from dims, init): init is a standard
# deviation for a matrix, or "gain" for a norm weight (1 + 0.1 N(0, 1), so
# a norm that ignores its weight shows)
LEAVES = (
    (("embed",), False, lambda d: (d["V"], d["D"]), lambda d: 1.0),
    (("final_norm",), False, lambda d: (d["D"],), "gain"),
    (("lm_head",), False, lambda d: (d["D"], d["V"]), lambda d: d["D"] ** -0.5),
    (("layers", "ln1"), True, lambda d: (d["D"],), "gain"),
    (("layers", "ln2"), True, lambda d: (d["D"],), "gain"),
    (("layers", "attn", "wq"), True, lambda d: (d["D"], d["H"] * d["hd"]),
     lambda d: d["D"] ** -0.5),
    (("layers", "attn", "wk"), True, lambda d: (d["D"], d["K"] * d["hd"]),
     lambda d: d["D"] ** -0.5),
    (("layers", "attn", "wv"), True, lambda d: (d["D"], d["K"] * d["hd"]),
     lambda d: d["D"] ** -0.5),
    (("layers", "attn", "wo"), True, lambda d: (d["H"] * d["hd"], d["D"]),
     lambda d: (d["H"] * d["hd"]) ** -0.5),
    (("layers", "mlp", "wi"), True, lambda d: (d["D"], d["F"]),
     lambda d: d["D"] ** -0.5),
    (("layers", "mlp", "wo"), True, lambda d: (d["F"], d["D"]),
     lambda d: d["F"] ** -0.5),
)


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number, all of its bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    seed >>= 32
    while seed:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
    return key


def _leaf(key, idx, layer, shape, init, d, dtype):
    k = jax.random.fold_in(key, idx)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    z = jax.random.normal(k, shape, jnp.float32)
    x = 1.0 + 0.1 * z if init == "gain" else z * init(d)
    return x.astype(dtype)


def _put(tree, path, value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def _make(d, dtype, key):
    out = {}
    for idx, (path, stacked, shape, init) in enumerate(LEAVES):
        if stacked:
            value = jax.vmap(lambda l, shape=shape, init=init, idx=idx: _leaf(
                key, idx, l, shape(d), init, d, dtype))(
                    jnp.arange(d["L"], dtype=jnp.uint32))
        else:
            value = _leaf(key, idx, None, shape(d), init, d, dtype)
        _put(out, path, value)
    return out


def _one_layer(d, dtype, key, layer):
    out = {}
    for idx, (path, stacked, shape, init) in enumerate(LEAVES):
        if stacked:
            _put(out, path[1:], _leaf(key, idx, layer, shape(d), init, d,
                                      dtype))
    return out


def _table(d, dtype, key, name):
    for idx, (path, stacked, shape, init) in enumerate(LEAVES):
        if path == (name,):
            return _leaf(key, idx, None, shape(d), init, d, dtype)
    raise KeyError(name)


@functools.lru_cache(maxsize=None)
def _jitted(kind: str, dkey: tuple, dtype: str, *static):
    d = dict(dkey)
    if kind == "all":
        return jax.jit(functools.partial(_make, d, dtype))
    if kind == "layer":
        return jax.jit(functools.partial(_one_layer, d, dtype))
    return jax.jit(lambda key: _table(d, dtype, key, static[0]))


def make_params(cfg: dict, seed: int, dtype: str):
    """The whole checkpoint, on the device, in one jitted call."""
    return _jitted("all", tuple(sorted(dims(cfg).items())), dtype)(
        seed_key(seed))


def layer_params(cfg: dict, seed: int, layer: int, dtype: str):
    """Layer ``layer`` of :func:`make_params`'s stacked leaves."""
    return _jitted("layer", tuple(sorted(dims(cfg).items())), dtype)(
        seed_key(seed), jnp.uint32(layer))


def table(cfg: dict, seed: int, name: str, dtype: str):
    """One unstacked leaf (``embed``, ``lm_head``, ``final_norm``)."""
    return _jitted("table", tuple(sorted(dims(cfg).items())), dtype, name)(
        seed_key(seed))
