"""Dense decoder with a SwiGLU MLP (llama-style: yi-9b), as the
configuration file states it (Hugging Face key names).  Test data: the CPU
tests put it in a checkout of their own to show that a second architecture
is a new file, and hold it to the program's ``forward`` at a smoke size.

    x = h + attn(rms_norm(h) * ln1),   h' = x + mlp(rms_norm(x) * ln2)
    attn: grouped-query causal softmax attention, rotary positions on
          ``rotary_fraction`` of each head (rotate-half pairing)
    mlp:  (silu(x wi_gate) * (x wi_up)) wo
    logits = (rms_norm(h) * final_norm) lm_head
"""

from __future__ import annotations

from typing import Iterable, List, Tuple

import jax
import jax.numpy as jnp

from harness.blocks import HI, causal_attention, rms_norm, rotary
from harness.weights import gain, normal

# Hugging Face's name of the MLP's activation -> the program's MLP kind
MLP = {"silu": "swiglu"}


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
            "head_dim": cfg["head_dim"],
            "num_layers": cfg["num_hidden_layers"],
            "vocab_size": cfg["vocab_size"], "mlp": MLP[cfg["hidden_act"]],
            "rope_theta": cfg["rope_theta"], "norm_eps": cfg["norm_eps"],
            "dtype": cfg["torch_dtype"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "family": "dense", "qkv_bias": False, "qk_norm": False,
            "attn_window": None, "mrope": False}


def _std_in(d):
    return d["D"] ** -0.5


LEAVES = (
    (("embed",), None, lambda d: (d["V"], d["D"]), normal(lambda d: 1.0)),
    (("final_norm",), None, lambda d: (d["D"],), gain),
    (("lm_head",), None, lambda d: (d["D"], d["V"]), normal(_std_in)),
    (("layers", "ln1"), "L", lambda d: (d["D"],), gain),
    (("layers", "ln2"), "L", lambda d: (d["D"],), gain),
    (("layers", "attn", "wq"), "L", lambda d: (d["D"], d["H"] * d["hd"]),
     normal(_std_in)),
    (("layers", "attn", "wk"), "L", lambda d: (d["D"], d["K"] * d["hd"]),
     normal(_std_in)),
    (("layers", "attn", "wv"), "L", lambda d: (d["D"], d["K"] * d["hd"]),
     normal(_std_in)),
    (("layers", "attn", "wo"), "L", lambda d: (d["H"] * d["hd"], d["D"]),
     normal(lambda d: (d["H"] * d["hd"]) ** -0.5)),
    (("layers", "mlp", "wi_gate"), "L", lambda d: (d["D"], d["F"]),
     normal(_std_in)),
    (("layers", "mlp", "wi_up"), "L", lambda d: (d["D"], d["F"]),
     normal(_std_in)),
    (("layers", "mlp", "wo"), "L", lambda d: (d["F"], d["D"]),
     normal(lambda d: d["F"] ** -0.5)),
)


def num_layers(d: dict) -> int:
    return d["L"]


def layer_at(d: dict, layer: int):
    return "dense", {"L": layer}


EMBED, FINAL_NORM, HEAD = "embed", "final_norm", ("lm_head", 1)
MATS = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
        ("mlp", "wi_gate"), ("mlp", "wi_up"), ("mlp", "wo"))


def embed(d, table, seqs):
    return table[seqs].astype(jnp.float32)


def _mm(x, w):
    return jnp.einsum("nta,ab->ntb", x, w, precision=HI)


def _layer(d, h, w):
    n, T, _ = h.shape
    x = rms_norm(h, w["ln1"], d["eps"])
    a = w["attn"]
    q = rotary(_mm(x, a["wq"]).reshape(n, T, d["H"], d["hd"]), d["theta"],
               d["fraction"])
    k = rotary(_mm(x, a["wk"]).reshape(n, T, d["K"], d["hd"]), d["theta"],
               d["fraction"])
    v = _mm(x, a["wv"]).reshape(n, T, d["K"], d["hd"])
    o = jax.lax.map(lambda qkv: causal_attention(*qkv), (q, k, v))
    h = h + _mm(o, a["wo"])
    x = rms_norm(h, w["ln2"], d["eps"])
    m = w["mlp"]
    u = jax.nn.silu(_mm(x, m["wi_gate"])) * _mm(x, m["wi_up"])
    return h + _mm(u, m["wo"])


LAYERS = {"dense": _layer}


def final(d, h, g):
    return rms_norm(h, g.astype(jnp.float32), d["eps"])


def logits(d, x, chunk):
    return jnp.matmul(x, chunk, precision=HI)


def layer_matmuls(cfg: dict) -> List[Tuple[int, int]]:
    """(contraction, output) width of each weight matrix of one layer:
    q, k, v, attention output, MLP gate, MLP up, MLP out."""
    d = dims(cfg)
    D, F, q, kv = d["D"], d["F"], d["H"] * d["hd"], d["K"] * d["hd"]
    return [(D, q), (D, kv), (D, kv), (q, D), (D, F), (D, F), (F, D)]


def _mat_params(cfg: dict) -> int:
    return sum(a * b for a, b in layer_matmuls(cfg))


def total_params(cfg: dict) -> int:
    d = dims(cfg)
    tables = d["V"] * d["D"] * (1 if cfg["tie_word_embeddings"] else 2)
    return d["L"] * (_mat_params(cfg) + 2 * d["D"]) + tables + d["D"]


def served_weight_bytes(cfg: dict, weights: str,
                        with_embedding: bool = False) -> int:
    d = dims(cfg)
    if weights == "bfloat16":
        mats = 2 * _mat_params(cfg)
    elif weights == "int8":
        mats = _mat_params(cfg) + 4 * sum(n for _, n in layer_matmuls(cfg))
    else:
        raise ValueError(weights)
    out = d["L"] * (mats + 2 * 2 * d["D"]) + 2 * d["D"] + 2 * d["D"] * d["V"]
    return out + (2 * d["D"] * d["V"] if with_embedding else 0)


def _kv_bytes(cfg: dict) -> int:
    d = dims(cfg)
    return 2 * d["L"] * d["K"] * d["hd"] * 2


def decode_flops(cfg: dict, lengths: Iterable[int]) -> float:
    d = dims(cfg)
    per_token = 2 * (d["L"] * _mat_params(cfg) + d["D"] * d["V"])
    attn = 4 * d["L"] * d["H"] * d["hd"]
    return float(sum(per_token + attn * (n + 1) for n in lengths))


def decode_bytes(cfg: dict, lengths: Iterable[int], weights: str) -> float:
    live = sum(n + 1 for n in lengths)
    return float(served_weight_bytes(cfg, weights) + live * _kv_bytes(cfg))


def prefill_flops(cfg: dict, S: int) -> float:
    d = dims(cfg)
    mats = 2 * d["L"] * _mat_params(cfg) * S
    attn = 4 * d["L"] * d["H"] * d["hd"] * S * (S + 1) / 2
    return float(mats + attn + 2 * d["D"] * d["V"])


def int8_calls(cfg: dict, M: int) -> List[Tuple[int, int, int]]:
    return [(M, a, b) for a, b in layer_matmuls(cfg)] * cfg["num_hidden_layers"]
