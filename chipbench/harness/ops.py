"""Operations and bytes the algorithm needs, from the model's shapes alone.

The counts of a model are its architecture module's (``harness.arch``):
each public count here sends the call to the module that the configuration
names, so a metric reader reads any architecture through the same names.
The configuration is the JSON file of the cell (Hugging Face key names).
What stays here is what no architecture changes: a kernel's operations and
bytes from its call shape, and the roofline time.
"""

from __future__ import annotations

from typing import Iterable, List, Tuple

from harness import arch


def total_params(cfg: dict) -> int:
    return arch.of(cfg).total_params(cfg)


def served_weight_bytes(cfg: dict, weights: str,
                        with_embedding: bool = False) -> int:
    """Bytes of the served weights in the format ``weights``; with
    ``with_embedding``, the embedding table too."""
    return arch.of(cfg).served_weight_bytes(cfg, weights, with_embedding)


def decode_flops(cfg: dict, lengths: Iterable[int]) -> float:
    """One decode step over the live slots; ``lengths`` are the positions
    each slot holds before the step."""
    return arch.of(cfg).decode_flops(cfg, lengths)


def decode_bytes(cfg: dict, lengths: Iterable[int], weights: str) -> float:
    """Bytes one decode step over the live slots has to move: the served
    weights it reads and the cached state of every live position."""
    return arch.of(cfg).decode_bytes(cfg, lengths, weights)


def prefill_flops(cfg: dict, S: int) -> float:
    """A batch-1 prefill of ``S`` tokens."""
    return arch.of(cfg).prefill_flops(cfg, S)


def int8_calls(cfg: dict, M: int) -> List[Tuple[int, int, int]]:
    """(M, D, N) of every ``int8_matmul`` call one model pass makes on ``M``
    rows."""
    return arch.of(cfg).int8_calls(cfg, M)


# parts of the counts that a module may give besides (the dense decoders do)
def layer_matmul_params(cfg: dict) -> int:
    return arch.of(cfg).layer_matmul_params(cfg)


def layer_params(cfg: dict) -> int:
    return arch.of(cfg).layer_params(cfg)


def kv_bytes_per_position(cfg: dict, kv_bytes: int = 2) -> int:
    return arch.of(cfg).kv_bytes_per_position(cfg, kv_bytes)


def int8_matmul_ops(M: int, D: int, N: int) -> float:
    return 2.0 * M * D * N


def int8_matmul_bytes(M: int, D: int, N: int, act_bytes: int = 2) -> float:
    """Activations in and out, int8 weights, one f32 scale per column."""
    return float(M * D * act_bytes + D * N + 4 * N + M * N * act_bytes)


def roofline_s(ops: float, nbytes: float, flops_per_s: float,
               bytes_per_s: float) -> float:
    return max(ops / flops_per_s, nbytes / bytes_per_s)
