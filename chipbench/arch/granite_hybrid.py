"""Mamba2 and attention layers interleaved by a pattern, each layer with its
own weights and a SwiGLU MLP, muP multipliers and a tied head
(granite-4.0-h, ``granitemoehybrid`` with no experts), as the configuration
file states it (Hugging Face key names).

    h = embed[tokens] * embedding_multiplier
    each layer:  h += residual_multiplier * mixer(rms_norm(h) * ln1)
                 h += residual_multiplier * mlp(rms_norm(h) * ln2)
    mixer: Mamba2 where ``layer_types`` says "mamba", else grouped-query
           causal attention with no positions (NoPE) and scores times
           ``attention_multiplier``
    mlp:   silu(x wi_gate) * (x wi_up) wo  (the two halves of
           ``input_linear``, gate first)
    logits = (rms_norm(h) * final_norm) embed^T / logits_scaling

Mamba2, one group of B and C:

    z, xBC, dt = x in_proj        xBC = silu(causal depthwise conv(xBC) + b)
    x, B, C = xBC                 dt = softplus(dt + dt_bias), A = -exp(A_log)
    h_t = exp(A dt_t) h_{t-1} + dt_t x_t B_t^T    (per head: hd x d_state)
    y_t = h_t C_t + D x_t         out = rms_norm(y * silu(z)) * gnorm out_proj

The reference runs that recurrence token by token, as written, and shares
nothing with the program's chunked form.  The counts are of the work a step
has to do: a decode step needs the served weights once, the keys and values
of each live position, and each live slot's recurrent state read and
written once; a prefill needs the recurrence once per token, not the
chunked form's extra products.  See ``harness/arch.py`` for each part.
"""

from __future__ import annotations

from typing import Iterable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.blocks import HI, causal_attention, rms_norm
from harness.weights import gain, normal

KINDS = ("mamba", "attention")
# the stack each kind's mixer leaves are stacked over (``dims`` keys)
STACK = {"mamba": "Lm", "attention": "La"}


def period(layer_types) -> tuple:
    """The shortest run of layer kinds that ``layer_types`` repeats."""
    types = tuple(layer_types)
    for p in range(1, len(types) + 1):
        if len(types) % p == 0 and types == types[:p] * (len(types) // p):
            return types[:p]
    raise AssertionError("unreachable")


def dims(cfg: dict) -> dict:
    types = tuple(cfg["layer_types"])
    if set(types) - set(KINDS):
        raise ValueError(f"unknown layer kinds {set(types) - set(KINDS)}")
    D, nh, mhd = cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    if nh * mhd != cfg["mamba_expand"] * D or cfg["mamba_n_groups"] != 1:
        raise ValueError("the program's Mamba2 has mamba_n_heads x "
                         "mamba_d_head = mamba_expand x hidden_size and one "
                         "group")
    return {"D": D, "F": cfg["shared_intermediate_size"],
            "H": cfg["num_attention_heads"],
            "K": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or D // cfg["num_attention_heads"],
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "types": types, "Lm": types.count("mamba"),
            "La": types.count("attention"),
            "nh": nh, "mhd": mhd, "di": nh * mhd, "N": cfg["mamba_d_state"],
            "W": cfg["mamba_d_conv"], "chunk": cfg["mamba_chunk_size"],
            "em": float(cfg["embedding_multiplier"]),
            "rm": float(cfg["residual_multiplier"]),
            "ls": float(cfg["logits_scaling"]),
            "am": float(cfg["attention_multiplier"]),
            "eps": float(cfg["rms_norm_eps"])}


def program_fields(cfg: dict) -> dict:
    d = dims(cfg)
    return {"family": "interleaved", "d_model": d["D"], "d_ff": d["F"],
            "num_heads": d["H"], "num_kv_heads": d["K"], "head_dim": d["hd"],
            "num_layers": d["L"], "vocab_size": d["V"],
            "mlp": {"silu": "swiglu"}[cfg["hidden_act"]],
            "layer_pattern": period(d["types"]),
            "ssm_state": d["N"], "ssm_head_dim": d["mhd"],
            "ssm_expand": cfg["mamba_expand"], "ssm_chunk": d["chunk"],
            "rope_theta": (0.0 if cfg["position_embedding_type"] == "nope"
                           else float(cfg["rope_theta"])),
            "attn_scale": d["am"], "embedding_multiplier": d["em"],
            "residual_multiplier": d["rm"], "logits_scaling": d["ls"],
            "norm_eps": d["eps"], "dtype": cfg["torch_dtype"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "qkv_bias": cfg["attention_bias"], "qk_norm": False,
            "attn_window": None}


# ---------------------------------------------------------------- weights
# matrices N(0, 1/fan_in), norm gains and D 1 + 0.1 N(0, 1); the conv
# N(0, 1/width) with bias N(0, 0.1^2); A_log 1 + 0.5 N(0, 1) (A about 1-7)
# and dt_bias -4 + 0.5 N(0, 1) (dt about 0.005-0.1 at a zero input), the
# ranges of Mamba2's own initialisation.  The tied table is N(0, EMBED_STD^2):
# the embedding multiplier puts 12 x a token's own row in the residual
# stream, whose product with that row in the tied head grows with the
# table's scale squared.  At N(0, 1) a token's own logit led every other by
# ~230 (the layers add a stream of RMS ~1.6), so every served token was the
# prompt's last and no reading could differ from the reference's; at 0.005
# the own row no longer leads, and the best logit depends on the context.
EMBED_STD = 0.005
def _offset(mean, std):
    return lambda z, d: mean + std * z


def _xbc(d):
    return d["di"] + 2 * d["N"]


LEAVES = (
    (("embed",), None, lambda d: (d["V"], d["D"]),
     normal(lambda d: EMBED_STD)),
    (("final_norm",), None, lambda d: (d["D"],), gain),
    (("layers", "ln1"), "L", lambda d: (d["D"],), gain),
    (("layers", "ln2"), "L", lambda d: (d["D"],), gain),
    (("layers", "mlp", "wi_gate"), "L", lambda d: (d["D"], d["F"]),
     normal(lambda d: d["D"] ** -0.5)),
    (("layers", "mlp", "wi_up"), "L", lambda d: (d["D"], d["F"]),
     normal(lambda d: d["D"] ** -0.5)),
    (("layers", "mlp", "wo"), "L", lambda d: (d["F"], d["D"]),
     normal(lambda d: d["F"] ** -0.5)),
    (("mamba_layers", "mamba", "in_proj"), "Lm",
     lambda d: (d["D"], d["di"] + _xbc(d) + d["nh"]),
     normal(lambda d: d["D"] ** -0.5)),
    (("mamba_layers", "mamba", "conv_w"), "Lm", lambda d: (d["W"], _xbc(d)),
     normal(lambda d: d["W"] ** -0.5)),
    (("mamba_layers", "mamba", "conv_b"), "Lm", lambda d: (_xbc(d),),
     normal(lambda d: 0.1)),
    (("mamba_layers", "mamba", "A_log"), "Lm", lambda d: (d["nh"],),
     _offset(1.0, 0.5)),
    (("mamba_layers", "mamba", "D_skip"), "Lm", lambda d: (d["nh"],), gain),
    (("mamba_layers", "mamba", "dt_bias"), "Lm", lambda d: (d["nh"],),
     _offset(-4.0, 0.5)),
    (("mamba_layers", "mamba", "gnorm_w"), "Lm", lambda d: (d["di"],), gain),
    (("mamba_layers", "mamba", "out_proj"), "Lm", lambda d: (d["di"], d["D"]),
     normal(lambda d: d["di"] ** -0.5)),
    (("attn_layers", "attn", "wq"), "La", lambda d: (d["D"], d["H"] * d["hd"]),
     normal(lambda d: d["D"] ** -0.5)),
    (("attn_layers", "attn", "wk"), "La", lambda d: (d["D"], d["K"] * d["hd"]),
     normal(lambda d: d["D"] ** -0.5)),
    (("attn_layers", "attn", "wv"), "La", lambda d: (d["D"], d["K"] * d["hd"]),
     normal(lambda d: d["D"] ** -0.5)),
    (("attn_layers", "attn", "wo"), "La", lambda d: (d["H"] * d["hd"], d["D"]),
     normal(lambda d: (d["H"] * d["hd"]) ** -0.5)),
)


def num_layers(d: dict) -> int:
    return d["L"]


def layer_at(d: dict, layer: int):
    kind = d["types"][layer]
    return kind, {"L": layer, STACK[kind]: d["types"][:layer].count(kind)}


# -------------------------------------------------------------- reference
EMBED, FINAL_NORM, HEAD = "embed", "final_norm", ("embed", 0)
MATS = (("mlp", "wi_gate"), ("mlp", "wi_up"), ("mlp", "wo"),
        ("mamba", "in_proj"), ("mamba", "out_proj"),
        ("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"))


def _mm(x, w):
    return jnp.einsum("ntd,de->nte", x, w, precision=HI)


def embed(d, table, seqs):
    return table[seqs].astype(jnp.float32) * d["em"]


def _mlp(d, h, w):
    x = rms_norm(h, w["ln2"], d["eps"])
    m = w["mlp"]
    u = jax.nn.silu(_mm(x, m["wi_gate"])) * _mm(x, m["wi_up"])
    return h + d["rm"] * _mm(u, m["wo"])


def _attention_layer(d, h, w):
    n, T, _ = h.shape
    x = rms_norm(h, w["ln1"], d["eps"])
    a = w["attn"]
    # causal_attention divides the scores by sqrt(hd): the query carries
    # the rest of attention_multiplier
    q = _mm(x, a["wq"]) * (d["am"] * np.sqrt(d["hd"]))
    q = q.reshape(n, T, d["H"], d["hd"])
    k = _mm(x, a["wk"]).reshape(n, T, d["K"], d["hd"])
    v = _mm(x, a["wv"]).reshape(n, T, d["K"], d["hd"])
    o = jax.lax.map(lambda qkv: causal_attention(*qkv), (q, k, v))
    return _mlp(d, h + d["rm"] * _mm(o, a["wo"]), w)


def _mamba_layer(d, h, w):
    n, T, _ = h.shape
    di, N, nh, mhd, W = d["di"], d["N"], d["nh"], d["mhd"], d["W"]
    x = rms_norm(h, w["ln1"], d["eps"])
    m = w["mamba"]
    zxbcdt = _mm(x, m["in_proj"])
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * N], \
        zxbcdt[..., 2 * di + 2 * N:]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, i:i + T] * m["conv_w"][i] for i in range(W))
                      + m["conv_b"])
    xs = xbc[..., :di].reshape(n, T, nh, mhd)
    Bs, Cs = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + m["dt_bias"])
    A = -jnp.exp(m["A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = jnp.exp(A * dt_t)[..., None, None] * s + \
            (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return s, jnp.einsum("nhps,ns->nhp", s, c_t, precision=HI)

    s0 = jnp.zeros((n, nh, mhd, N), jnp.float32)
    _, ys = jax.lax.scan(step, s0, tuple(a.swapaxes(0, 1)
                                         for a in (xs, Bs, Cs, dt)))
    y = ys.swapaxes(0, 1) + m["D_skip"][:, None] * xs
    y = rms_norm(y.reshape(n, T, di) * jax.nn.silu(z), m["gnorm_w"],
                 d["eps"])
    return _mlp(d, h + d["rm"] * _mm(y, m["out_proj"]), w)


LAYERS = {"mamba": _mamba_layer, "attention": _attention_layer}


def final(d, h, g):
    return rms_norm(h, g.astype(jnp.float32), d["eps"])


def logits(d, x, chunk):
    return jnp.matmul(x, chunk.T, precision=HI) / d["ls"]


# ----------------------------------------------------------------- counts
def layer_matmuls(cfg: dict, kind: str) -> List[Tuple[int, int]]:
    """(contraction, output) width of each weight matrix of one layer of
    ``kind``: its mixer's, then the MLP's gate, up and down."""
    d = dims(cfg)
    D, F = d["D"], d["F"]
    if kind == "mamba":
        mixer = [(D, d["di"] + _xbc(d) + d["nh"]), (d["di"], D)]
    else:
        q, kv = d["H"] * d["hd"], d["K"] * d["hd"]
        mixer = [(D, q), (D, kv), (D, kv), (q, D)]
    return mixer + [(D, F), (D, F), (F, D)]


def _matmul_params(cfg: dict) -> int:
    d = dims(cfg)
    return sum(a * b for kind in d["types"]
               for a, b in layer_matmuls(cfg, kind))


def _vectors(cfg: dict) -> int:
    """Every parameter outside the matrices and the table: norm gains, the
    conv, A_log, D and dt_bias."""
    d = dims(cfg)
    mamba = d["W"] * _xbc(d) + _xbc(d) + 3 * d["nh"] + d["di"]
    return d["L"] * 2 * d["D"] + d["Lm"] * mamba + d["D"]


def total_params(cfg: dict) -> int:
    d = dims(cfg)
    tables = d["V"] * d["D"] * (1 if cfg["tie_word_embeddings"] else 2)
    return _matmul_params(cfg) + _vectors(cfg) + tables


def served_weight_bytes(cfg: dict, weights: str,
                        with_embedding: bool = False) -> int:
    """Bytes of the served weights, every one in the float format
    ``weights``, the tied table counted once, as the output head
    (``with_embedding`` adds nothing: the table is the head).  No int8
    format of this architecture is served."""
    if weights == "int8":
        raise ValueError("granite_hybrid has no int8 format")
    return jnp.dtype(weights).itemsize * total_params(cfg)


def kv_bytes_per_position(cfg: dict, kv_bytes: int = 2) -> int:
    d = dims(cfg)
    return 2 * d["La"] * d["K"] * d["hd"] * kv_bytes


def state_bytes_per_slot(cfg: dict) -> int:
    """A slot's recurrent state: per Mamba layer, the conv's last
    ``mamba_d_conv - 1`` inputs in the served dtype and the SSM state in
    float32."""
    d = dims(cfg)
    conv = (d["W"] - 1) * _xbc(d) * jnp.dtype(cfg["torch_dtype"]).itemsize
    ssm = d["nh"] * d["mhd"] * d["N"] * 4
    return d["Lm"] * (conv + ssm)


def _ssm_flops_per_token(d: dict) -> int:
    """The recurrence on one token, all Mamba layers: decay, input and
    update (3), read-out (2) per state entry; the conv (2 per tap)."""
    return d["Lm"] * (5 * d["nh"] * d["mhd"] * d["N"]
                      + 2 * d["W"] * _xbc(d))


def decode_flops(cfg: dict, lengths: Iterable[int]) -> float:
    """One decode step over the live slots; ``lengths`` are the positions
    each slot holds before the step (it attends over ``length + 1``)."""
    d = dims(cfg)
    per_token = (2 * (_matmul_params(cfg) + d["D"] * d["V"])
                 + _ssm_flops_per_token(d))
    attn = 4 * d["La"] * d["H"] * d["hd"]
    return float(sum(per_token + attn * (n + 1) for n in lengths))


def decode_bytes(cfg: dict, lengths: Iterable[int], weights: str) -> float:
    """Served weights (the tied table once, as the head) read once, the keys
    and values of every live position, and each live slot's recurrent state
    read and written."""
    lengths = list(lengths)
    live = sum(n + 1 for n in lengths)
    return float(served_weight_bytes(cfg, weights)
                 + live * kv_bytes_per_position(cfg)
                 + 2 * len(lengths) * state_bytes_per_slot(cfg))


def prefill_flops(cfg: dict, S: int) -> float:
    """A batch-1 prefill of ``S`` tokens: the layers on every token, causal
    attention (the lower triangle), the recurrence once per token, the
    output head on the last token."""
    d = dims(cfg)
    mats = 2 * _matmul_params(cfg) * S
    attn = 4 * d["La"] * d["H"] * d["hd"] * S * (S + 1) / 2
    return float(mats + attn + _ssm_flops_per_token(d) * S
                 + 2 * d["D"] * d["V"])


def int8_calls(cfg: dict, M: int) -> List[Tuple[int, int, int]]:
    """No int8 format of this architecture is served."""
    return []
