"""Operations and bytes the algorithm needs, from the model's shapes alone.

These count the work a step has to do, not what one implementation does: a
decode step needs the served weights once and the keys and values of each
live position, not a whole ``max_seq`` slab; causal attention needs the lower
triangle of its scores.  The configuration is the JSON file of the cell
(Hugging Face key names).
"""

from __future__ import annotations

from typing import Iterable, List, Tuple


def dims(cfg: dict) -> dict:
    return {"D": cfg["hidden_size"], "F": cfg["intermediate_size"],
            "H": cfg["num_attention_heads"],
            "K": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"]}


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


def int8_matmul_ops(M: int, D: int, N: int) -> float:
    return 2.0 * M * D * N


def int8_matmul_bytes(M: int, D: int, N: int, act_bytes: int = 2) -> float:
    """Activations in and out, int8 weights, one f32 scale per column."""
    return float(M * D * act_bytes + D * N + 4 * N + M * N * act_bytes)


def roofline_s(ops: float, nbytes: float, flops_per_s: float,
               bytes_per_s: float) -> float:
    return max(ops / flops_per_s, nbytes / bytes_per_s)
