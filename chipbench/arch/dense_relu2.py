"""Dense decoder with a squared-ReLU MLP (minitron-4b, Nemotron-style), as
the configuration file states it (Hugging Face key names).

    x = h + attn(rms_norm(h) * ln1),   h' = x + mlp(rms_norm(x) * ln2)
    attn: grouped-query causal softmax attention, rotary positions on
          ``rotary_fraction`` of each head (rotate-half pairing)
    mlp:  relu(x wi)^2 wo
    logits = (rms_norm(h) * final_norm) lm_head

Every layer is of one kind, its leaves stacked over ``L``.  The counts are
of the work a step has to do, not of what one implementation does: a decode
step needs the served weights once and the keys and values of each live
position, not a whole ``max_seq`` slab; causal attention needs the lower
triangle of its scores.  See ``harness/arch.py`` for what each part is.
"""

from __future__ import annotations

from typing import Iterable, List, Tuple

import jax
import jax.numpy as jnp

from harness.blocks import HI, causal_attention, rms_norm, rotary
from harness.weights import gain, normal


def dims(cfg: dict) -> dict:
    return {"D": cfg["hidden_size"], "F": cfg["intermediate_size"],
            "H": cfg["num_attention_heads"],
            "K": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "theta": float(cfg["rope_theta"]),
            "fraction": float(cfg["rotary_fraction"]),
            "eps": float(cfg["norm_eps"])}


def program_fields(cfg: dict) -> dict:
    return {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "num_layers":
                cfg["num_hidden_layers"], "vocab_size": cfg["vocab_size"],
            "mlp": cfg["hidden_act"], "rope_theta": cfg["rope_theta"],
            "norm_eps": cfg["norm_eps"], "dtype": cfg["torch_dtype"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "family": "dense", "qkv_bias": False, "qk_norm": False,
            "attn_window": None, "mrope": False}


# ---------------------------------------------------------------- weights
# matrices N(0, 1/fan_in), the embedding N(0, 1), norm gains 1 + 0.1 N(0, 1)
LEAVES = (
    (("embed",), None, lambda d: (d["V"], d["D"]), normal(lambda d: 1.0)),
    (("final_norm",), None, lambda d: (d["D"],), gain),
    (("lm_head",), None, lambda d: (d["D"], d["V"]),
     normal(lambda d: d["D"] ** -0.5)),
    (("layers", "ln1"), "L", lambda d: (d["D"],), gain),
    (("layers", "ln2"), "L", lambda d: (d["D"],), gain),
    (("layers", "attn", "wq"), "L", lambda d: (d["D"], d["H"] * d["hd"]),
     normal(lambda d: d["D"] ** -0.5)),
    (("layers", "attn", "wk"), "L", lambda d: (d["D"], d["K"] * d["hd"]),
     normal(lambda d: d["D"] ** -0.5)),
    (("layers", "attn", "wv"), "L", lambda d: (d["D"], d["K"] * d["hd"]),
     normal(lambda d: d["D"] ** -0.5)),
    (("layers", "attn", "wo"), "L", lambda d: (d["H"] * d["hd"], d["D"]),
     normal(lambda d: (d["H"] * d["hd"]) ** -0.5)),
    (("layers", "mlp", "wi"), "L", lambda d: (d["D"], d["F"]),
     normal(lambda d: d["D"] ** -0.5)),
    (("layers", "mlp", "wo"), "L", lambda d: (d["F"], d["D"]),
     normal(lambda d: d["F"] ** -0.5)),
)


def num_layers(d: dict) -> int:
    return d["L"]


def layer_at(d: dict, layer: int):
    return "dense", {"L": layer}


# -------------------------------------------------------------- reference
EMBED, FINAL_NORM, HEAD = "embed", "final_norm", ("lm_head", 1)
MATS = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
        ("mlp", "wi"), ("mlp", "wo"))


def embed(d, table, seqs):
    return table[seqs].astype(jnp.float32)


def _layer(d, h, w):
    n, T, _ = h.shape
    theta, fraction, eps = d["theta"], d["fraction"], d["eps"]
    x = rms_norm(h, w["ln1"], eps)
    a = w["attn"]
    q = jnp.einsum("ntd,de->nte", x, a["wq"], precision=HI)
    k = jnp.einsum("ntd,de->nte", x, a["wk"], precision=HI)
    v = jnp.einsum("ntd,de->nte", x, a["wv"], precision=HI)
    q = rotary(q.reshape(n, T, d["H"], d["hd"]), theta, fraction)
    k = rotary(k.reshape(n, T, d["K"], d["hd"]), theta, fraction)
    v = v.reshape(n, T, d["K"], d["hd"])
    o = jax.lax.map(lambda qkv: causal_attention(*qkv), (q, k, v))
    h = h + jnp.einsum("nte,ed->ntd", o, a["wo"], precision=HI)
    x = rms_norm(h, w["ln2"], eps)
    m = w["mlp"]
    u = jnp.square(jax.nn.relu(jnp.einsum("ntd,df->ntf", x, m["wi"],
                                          precision=HI)))
    return h + jnp.einsum("ntf,fd->ntd", u, m["wo"], precision=HI)


LAYERS = {"dense": _layer}


def final(d, h, g):
    return rms_norm(h, g.astype(jnp.float32), d["eps"])


def logits(d, x, chunk):
    return jnp.matmul(x, chunk, precision=HI)


# ----------------------------------------------------------------- counts
def layer_matmuls(cfg: dict) -> List[Tuple[int, int]]:
    """(contraction, output) width of each weight matrix of one layer:
    q, k, v, attention output, MLP in, MLP out (squared-ReLU MLP)."""
    d = dims(cfg)
    D, F, q, kv = d["D"], d["F"], d["H"] * d["hd"], d["K"] * d["hd"]
    return [(D, q), (D, kv), (D, kv), (q, D), (D, F), (F, D)]


def layer_matmul_params(cfg: dict) -> int:
    return sum(a * b for a, b in layer_matmuls(cfg))


def layer_params(cfg: dict) -> int:
    """Matrices plus the two norm gains."""
    return layer_matmul_params(cfg) + 2 * cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    d = dims(cfg)
    tables = d["V"] * d["D"] * (1 if cfg["tie_word_embeddings"] else 2)
    return d["L"] * layer_params(cfg) + tables + d["D"]


def served_weight_bytes(cfg: dict, weights: str,
                        with_embedding: bool = False) -> int:
    """Bytes of the served weights: bf16 everywhere, or (``int8``) the layer
    matrices as int8 with one f32 scale per output channel."""
    d = dims(cfg)
    if weights == "bfloat16":
        mats = 2 * layer_matmul_params(cfg)
    elif weights == "int8":
        mats = layer_matmul_params(cfg) + 4 * sum(
            n for _, n in layer_matmuls(cfg))
    else:
        raise ValueError(weights)
    out = d["L"] * (mats + 2 * 2 * d["D"]) + 2 * d["D"]   # + norms
    out += 2 * d["D"] * d["V"]                            # output head
    if with_embedding:
        out += 2 * d["D"] * d["V"]
    return out


def kv_bytes_per_position(cfg: dict, kv_bytes: int = 2) -> int:
    d = dims(cfg)
    return 2 * d["L"] * d["K"] * d["hd"] * kv_bytes


def decode_flops(cfg: dict, lengths: Iterable[int]) -> float:
    """One decode step over the live slots; ``lengths`` are the positions
    each slot holds before the step (it attends over ``length + 1``)."""
    d = dims(cfg)
    per_token = 2 * (d["L"] * layer_matmul_params(cfg) + d["D"] * d["V"])
    attn = 4 * d["L"] * d["H"] * d["hd"]
    return float(sum(per_token + attn * (n + 1) for n in lengths))


def decode_bytes(cfg: dict, lengths: Iterable[int], weights: str) -> float:
    """Served weights except the embedding table, read once, plus the keys
    and values of every live position."""
    live = sum(n + 1 for n in lengths)
    return float(served_weight_bytes(cfg, weights)
                 + live * kv_bytes_per_position(cfg))


def prefill_flops(cfg: dict, S: int) -> float:
    """A batch-1 prefill of ``S`` tokens: the layers on every token, causal
    attention (the lower triangle), the output head on the last token."""
    d = dims(cfg)
    mats = 2 * d["L"] * layer_matmul_params(cfg) * S
    attn = 4 * d["L"] * d["H"] * d["hd"] * S * (S + 1) / 2
    return float(mats + attn + 2 * d["D"] * d["V"])


def int8_calls(cfg: dict, M: int) -> List[Tuple[int, int, int]]:
    """(M, D, N) of every ``int8_matmul`` call one model pass makes on ``M``
    rows: each layer matrix of each layer."""
    return [(M, a, b) for a, b in layer_matmuls(cfg)] * cfg["num_hidden_layers"]
