"""Random weights from the seed, made by the benchmark and not the program.

The weights are the checkpoint the program is deployed from, in the tree its
model loads, as the configuration's architecture module lists the leaves
(``harness.arch``): each with its path, the layer count it is stacked over
(none for a leaf made once), its shape and its init.  The reference
regenerates any one layer of them from the same seed on its own.  Every leaf
draws from a key of its own, and each layer of a stacked leaf from a key of
its own, so a layer made alone equals that layer of the whole tree, bit for
bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness import arch


def gain(z, d):
    """A norm weight, 1 + 0.1 N(0, 1), so a norm that ignores it shows."""
    return 1.0 + 0.1 * z


def normal(std):
    """N(0, std(d)^2), as a matrix or a table is drawn."""
    return lambda z, d: z * std(d)


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
    return init(z, d).astype(dtype)


def _put(tree, path, value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def _make(A, d, dtype, key):
    out = {}
    for idx, (path, stack, shape, init) in enumerate(A.LEAVES):
        if stack is not None:
            value = jax.vmap(lambda l, shape=shape, init=init, idx=idx: _leaf(
                key, idx, l, shape(d), init, d, dtype))(
                    jnp.arange(d[stack], dtype=jnp.uint32))
        else:
            value = _leaf(key, idx, None, shape(d), init, d, dtype)
        _put(out, path, value)
    return out


def _one_layer(A, d, dtype, stacks, key, at):
    out = {}
    for idx, (path, stack, shape, init) in enumerate(A.LEAVES):
        if stack in stacks:
            _put(out, path[1:], _leaf(key, idx, at[stacks.index(stack)],
                                      shape(d), init, d, dtype))
    return out


def _table(A, d, dtype, key, name):
    for idx, (path, stack, shape, init) in enumerate(A.LEAVES):
        if path == (name,):
            return _leaf(key, idx, None, shape(d), init, d, dtype)
    raise KeyError(name)


@functools.lru_cache(maxsize=None)
def _jitted(kind: str, A, dkey: tuple, dtype: str, *static):
    d = dict(dkey)
    if kind == "all":
        return jax.jit(functools.partial(_make, A, d, dtype))
    if kind == "layer":
        return jax.jit(functools.partial(_one_layer, A, d, dtype, static))
    return jax.jit(lambda key: _table(A, d, dtype, key, static[0]))


def _arch(cfg: dict):
    A = arch.of(cfg)
    return A, tuple(sorted(A.dims(cfg).items()))


def make_params(cfg: dict, seed: int, dtype: str):
    """The whole checkpoint, on the device, in one jitted call."""
    A, dkey = _arch(cfg)
    return _jitted("all", A, dkey, dtype)(seed_key(seed))


def layer_params(cfg: dict, seed: int, layer: int, dtype: str):
    """Layer ``layer`` of :func:`make_params`'s stacked leaves: its slice of
    each stack it reads, each leaf under its path less the first key."""
    A, dkey = _arch(cfg)
    _, where = A.layer_at(dict(dkey), layer)
    stacks = tuple(sorted(where))
    return _jitted("layer", A, dkey, dtype, *stacks)(
        seed_key(seed), tuple(jnp.uint32(where[s]) for s in stacks))


def table(cfg: dict, seed: int, name: str, dtype: str):
    """One leaf made once (an embedding, an output table, a final norm)."""
    A, dkey = _arch(cfg)
    return _jitted("table", A, dkey, dtype, name)(seed_key(seed))
