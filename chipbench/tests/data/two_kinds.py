"""A toy architecture with layers of two kinds, for the CPU tests: the shape
that hybrid and mixture-of-experts configurations need.  Layers listed in
``b_layers`` are of kind ``b``, the others of kind ``a``; each kind's leaves
are stacked over that kind's count, and a norm gain over every layer.  The
output table is the embedding (tied).

    a: h + tanh((rms_norm(h) * norm) w) * g
    b: h + (rms_norm(h) * norm) u
"""

import jax.numpy as jnp

from harness.blocks import HI, rms_norm
from harness.weights import gain, normal


def dims(cfg):
    b = tuple(cfg["b_layers"])
    L = cfg["num_hidden_layers"]
    return {"D": cfg["hidden_size"], "V": cfg["vocab_size"], "L": L,
            "La": L - len(b), "Lb": len(b), "b": b,
            "eps": float(cfg["norm_eps"])}


LEAVES = (
    (("embed",), None, lambda d: (d["V"], d["D"]), normal(lambda d: 1.0)),
    (("final_norm",), None, lambda d: (d["D"],), gain),
    (("norms", "norm"), "L", lambda d: (d["D"],), gain),
    (("a_layers", "w"), "La", lambda d: (d["D"], d["D"]),
     normal(lambda d: d["D"] ** -0.5)),
    (("a_layers", "g"), "La", lambda d: (d["D"],), gain),
    (("b_layers", "u"), "Lb", lambda d: (d["D"], d["D"]),
     normal(lambda d: d["D"] ** -0.5)),
)


def num_layers(d):
    return d["L"]


def layer_at(d, layer):
    if layer in d["b"]:
        return "b", {"L": layer, "Lb": d["b"].index(layer)}
    return "a", {"L": layer,
                 "La": layer - sum(1 for i in d["b"] if i < layer)}


EMBED, FINAL_NORM, HEAD = "embed", "final_norm", ("embed", 0)
MATS = (("w",), ("u",))


def embed(d, table, seqs):
    return table[seqs].astype(jnp.float32)


def _a(d, h, w):
    x = rms_norm(h, w["norm"], d["eps"])
    return h + jnp.tanh(jnp.einsum("ntd,de->nte", x, w["w"],
                                   precision=HI)) * w["g"]


def _b(d, h, w):
    x = rms_norm(h, w["norm"], d["eps"])
    return h + jnp.einsum("ntd,de->nte", x, w["u"], precision=HI)


LAYERS = {"a": _a, "b": _b}


def final(d, h, g):
    return rms_norm(h, g.astype(jnp.float32), d["eps"])


def logits(d, x, chunk):
    return jnp.matmul(x, chunk.T, precision=HI)
