"""TD2 'Model format': the serialized forms a model is served from.

Three formats, mirroring the paper's native / converted / optimized split:

  * ``native``   — framework-native: one ``.npz`` of the flattened pytree
                   (the TF-SavedModel / torch state_dict analogue).
  * ``rsm``      — repro-saved-model: a manifest.json (tree structure, dtypes,
                   shapes, offsets) + a single raw tensors.bin, mmap-friendly
                   zero-copy load (the ONNX/TorchScript-style interchange
                   format; interoperable because the manifest is the contract).
  * ``rsm_int8`` — optimized serving format: 2-D matmul weights stored as
                   per-output-channel symmetric int8 + f32 scales (the
                   TensorRT/TFLite-engine analogue).  Loads either dequantized
                   (portable path) or as ``QTensor`` leaves consumed by the
                   Pallas ``int8_matmul`` kernel (runtime-engine path).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.int8_matmul import quantize_int8

# -- QTensor: a quantized leaf the model's dense() dispatches on ---------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    wq: Any                       # (D, N) int8
    scales: Any                   # (N,) f32

    @property
    def shape(self):
        return self.wq.shape

    @property
    def ndim(self):
        return self.wq.ndim

    def dequant(self):
        return (
            self.wq.astype(jnp.float32) * self.scales[..., None, :]
        ).astype(jnp.bfloat16)

    def tree_flatten(self):
        return (self.wq, self.scales), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _key(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _flatten(params) -> Dict[str, np.ndarray]:
    return {_key(path): np.asarray(leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(params)[0]}


def _unflatten(template, flat: Dict[str, np.ndarray]):
    paths = jax.tree_util.tree_flatten_with_path(template)[0]
    treedef = jax.tree_util.tree_structure(template)
    leaves = [jnp.asarray(flat[_key(path)]) for path, _ in paths]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# -- native (npz) ---------------------------------------------------------------


def save_native(params, path: str) -> int:
    flat = {
        k: (v.astype(np.float32) if v.dtype == jnp.bfloat16 else v)
        for k, v in _flatten(params).items()
    }
    np.savez(path, **flat)
    return os.path.getsize(path if path.endswith(".npz") else path + ".npz")


def load_native(template, path: str):
    if not path.endswith(".npz"):
        path += ".npz"
    with np.load(path) as z:
        flat = {k: z[k] for k in z.files}
    return _unflatten(template, flat)


# -- rsm (manifest + raw bin) ----------------------------------------------------


# the modules whose weights layers.dense() consumes: only those may become
# QTensor leaves.  Embeddings are gathered, routers need f32 logits, and
# norms, biases and recurrent parameters never reach dense().
_DENSE_MODULES = ("attn", "xattn", "mlp", "dense_mlp")


def _quantizable(key: str, leaf) -> bool:
    *_, module, name = ("",) + tuple(key.split("/"))
    return (
        module in _DENSE_MODULES
        and name.startswith("w")
        and leaf.ndim in (2, 3)  # (D, N), or stacked layers (L, D, N)
        and leaf.shape[-2] >= 8
        and str(leaf.dtype) in ("float32", "float16", "bfloat16")
    )


def _write(f, leaf) -> int:
    """Write ``leaf``'s raw bytes (one host copy at most); returns the count."""
    a = np.ascontiguousarray(np.asarray(leaf))
    return f.write(a.reshape(-1).view(np.uint8).data)


def save_rsm(params, path: str, quantize: bool = False) -> int:
    """Returns total bytes on disk. ``quantize`` -> rsm_int8.

    Tensors are written one at a time, each in its own dtype (bf16 stays
    bf16), so the host holds one tensor and never the whole tree.  Stacked
    weights quantize one (D, N) layer slice at a time: the device holds a
    slice and its f32 temporaries, never a second copy of the weights.
    """
    os.makedirs(path, exist_ok=True)
    manifest = {"format": "rsm_int8" if quantize else "rsm", "tensors": {}}
    offset = 0
    leaves = sorted(
        ((_key(p), leaf)
         for p, leaf in jax.tree_util.tree_flatten_with_path(params)[0]),
        key=lambda kv: kv[0])
    with open(os.path.join(path, "tensors.bin"), "wb") as f:
        for key, leaf in leaves:
            entry = {"shape": list(leaf.shape), "offset": offset,
                     "orig_dtype": str(leaf.dtype)}
            if quantize and _quantizable(key, leaf):
                slices = [leaf] if leaf.ndim == 2 else list(leaf)
                scales = []
                for w in slices:
                    wq, sc = quantize_int8(jnp.asarray(w))
                    offset += _write(f, wq)
                    scales.append(np.asarray(sc))
                entry.update(dtype="int8", quantized=True,
                             scales_offset=offset)
                offset += _write(f, np.stack(scales))
            else:
                entry.update(dtype=str(leaf.dtype), quantized=False)
                offset += _write(f, leaf)
            manifest["tensors"][key] = entry
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return sum(
        os.path.getsize(os.path.join(path, n))
        for n in ("tensors.bin", "manifest.json")
    )


def load_rsm(template, path: str, as_qtensor: bool = False):
    """Load an rsm/rsm_int8 directory.

    as_qtensor=True keeps int8 weights as QTensor leaves (runtime-engine
    path); otherwise they are dequantized to the original dtype (portable).
    """
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    buf = np.memmap(os.path.join(path, "tensors.bin"), dtype=np.uint8, mode="r")

    def _own(view: np.ndarray) -> np.ndarray:
        # frombuffer on the memmap returns a VIEW of the file, and on CPU
        # jnp.asarray may alias it zero-copy — a later overwrite of the
        # registry entry would then mutate already-loaded engine weights
        # in place.  Copy so every loaded tree owns its memory.
        return np.array(view)

    paths = jax.tree_util.tree_flatten_with_path(template)[0]
    treedef = jax.tree_util.tree_structure(template)
    leaves = []
    for path_keys, _ in paths:
        e = manifest["tensors"][_key(path_keys)]
        shape = tuple(e["shape"])
        if e["quantized"]:
            n = int(np.prod(shape))
            wq = _own(np.frombuffer(
                buf, np.int8, count=n, offset=e["offset"]
            ).reshape(shape))
            scales_shape = shape[:-2] + shape[-1:]
            scales = _own(np.frombuffer(
                buf, np.float32, count=int(np.prod(scales_shape)),
                offset=e["scales_offset"],
            ).reshape(scales_shape))
            if as_qtensor:
                leaves.append(QTensor(jnp.asarray(wq), jnp.asarray(scales)))
            else:
                leaves.append(
                    (jnp.asarray(wq, jnp.float32)
                     * jnp.asarray(scales)[..., None, :])
                    .astype(jnp.dtype(e["orig_dtype"]))
                )
        else:
            dt = jnp.dtype(e["dtype"])
            n = int(np.prod(shape)) if shape else 1
            arr = _own(
                np.frombuffer(buf, dt, count=n, offset=e["offset"]).reshape(
                    shape
                )
            )
            leaves.append(jnp.asarray(arr, jnp.dtype(e["orig_dtype"])))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def format_size_bytes(params, fmt: str, tmpdir: str) -> int:
    """Bytes-on-disk for a format (TD2 interoperability/footprint metric)."""
    if fmt == "native":
        return save_native(params, os.path.join(tmpdir, "m.npz"))
    if fmt == "rsm":
        return save_rsm(params, os.path.join(tmpdir, "rsm"), quantize=False)
    if fmt == "rsm_int8":
        return save_rsm(params, os.path.join(tmpdir, "rsm8"), quantize=True)
    raise ValueError(fmt)
