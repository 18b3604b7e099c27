"""A configuration's architecture: a module of its own, named by the file.

A configuration file names its architecture (``"architecture": "<name>"``),
and the module is ``chipbench/arch/<name>.py`` in the checkout the file was
read from (``Bench.config`` notes the directory under ``ARCH_DIR_KEY``; a
configuration made in code finds this checkout's).  A new architecture is a
new file there, and no harness file changes.  The module describes the
architecture as the configuration file states it:

- ``dims(cfg)``: the sizes and constants its other parts read, as a dict of
  hashable values;
- ``program_fields(cfg)``: the fields of the program's model configuration
  that the file implies, which ``cell.program_config`` compares;
- the weights tree (``harness.weights``): ``LEAVES``, one ``(path, stack,
  shape, init)`` per leaf, where ``stack`` is None for a leaf made once, else
  the key of ``dims`` counting the layers that the leaf is stacked over;
  ``shape(d)`` is the shape of one layer's slice, and ``init(z, d)`` maps a
  standard normal draw ``z`` of that shape to the leaf;
- its layers: ``num_layers(d)``, and ``layer_at(d, l)`` -> ``(kind,
  {stack: index})``, the layer function of layer ``l`` and where that
  layer's slice sits in each stack it reads;
- its reference (``harness.reference``): ``EMBED`` and ``FINAL_NORM``, the
  names of those leaves; ``HEAD``, the output table's leaf and its vocabulary
  axis (a tied model names its embedding); ``embed(d, table, seqs)``;
  ``LAYERS``, kind -> ``f(d, h, w)`` on a layer's leaves in float32;
  ``final(d, h, g)``; ``logits(d, x, chunk)`` over a float32 slice of the
  output table; and ``MATS``, the paths in a layer's leaves that an int8
  configuration and the lower-precision control quantize;
- its counts (``harness.ops``): ``decode_flops``, ``decode_bytes``,
  ``prefill_flops``, ``served_weight_bytes``, ``int8_calls`` and
  ``total_params``.
"""

from __future__ import annotations

import functools
import pathlib
from types import ModuleType

from harness.spec import ARCH_DIR_KEY, ROOT, load_module

DEFAULT_DIR = ROOT / "chipbench" / "arch"


def of(cfg: dict) -> ModuleType:
    """The module of the architecture that ``cfg`` names."""
    name = cfg.get("architecture")
    if not name:
        raise KeyError(f"configuration {cfg.get('name')!r} names no "
                       "architecture: give it an \"architecture\" key, the "
                       "name of a module under chipbench/arch/")
    return _load(str(cfg.get(ARCH_DIR_KEY, DEFAULT_DIR)), name)


@functools.lru_cache(maxsize=None)
def _load(directory: str, name: str) -> ModuleType:
    return load_module(pathlib.Path(directory) / f"{name}.py",
                       "architecture", name)
