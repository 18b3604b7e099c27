"""A benchmark checkout in miniature for the CPU tests: the real readers and
``BENCHMARK.json`` layout, a two-layer minitron cut and a small chat mix."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

CB = pathlib.Path(__file__).resolve().parents[1]
ROOT = CB.parent
for p in (str(CB), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "name": "tiny", "model_type": "nemotron", "hidden_size": 256,
    "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 32, "num_hidden_layers": 2,
    "vocab_size": 1024, "hidden_act": "relu2", "norm_eps": 1e-05,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "rope_theta": 1000000.0, "rotary_fraction": 1.0, "norm_type": "rmsnorm",
    "weights": "float32", "control_bits": 8,
    "serving": {"arch": "minitron-4b-smoke", "format": "rsm"},
}

# the same model served by the program's own weight-only int8 path
TINY_INT8 = dict(TINY, name="tiny-int8", weights="int8", control_bits=4,
                 serving={"arch": "minitron-4b-smoke", "format": "rsm_int8"})

CHAT = {
    "arrivals": {"process": "poisson", "rate_per_s": 12.0},
    "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.8,
                   "min": 8, "max": 32, "round": "pow2_log"},
    "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                   "min": 3, "max": 12},
    "slots": 4, "max_seq": 64, "drain_cap_s": 20.0, "check_sample": 4,
    "slo": {"ttft_s": 1.0, "gap_s": 0.1},
}

def make_checkout(tmp: pathlib.Path, limits=None, config=TINY,
                  **chat) -> pathlib.Path:
    """A checkout root with the real metric readers and one tiny cell,
    ``tiny.chat``, serving ``config`` and held to ``limits`` (name ->
    limit); ``chat`` overrides keys of the mix."""
    cb = tmp / "chipbench"
    for d in ("configs", "traffic", "limits"):
        (cb / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(CB / "metrics", cb / "metrics", dirs_exist_ok=True)
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cb / "configs" / "tiny.json").write_text(json.dumps(config))
    (cb / "traffic" / "chat.json").write_text(json.dumps(dict(CHAT, **chat)))
    limits = limits or {"logit_gap": 1e-3}
    (cb / "limits" / "tiny.chat.json").write_text(
        json.dumps({k: {"limit": v} for k, v in limits.items()}))
    rename = {"minitron4b.chat": "tiny.chat"}
    for group in ("end_to_end", "per_layer"):
        for m in real[group]:
            if "workloads" in m:
                m["workloads"] = [rename[w] for w in m["workloads"]
                                  if w in rename]
    spec = dict(real,
                configs=[{"name": "tiny", "source": "test",
                          "file": "chipbench/configs/tiny.json",
                          "reduced": [], "why": "test"}],
                workloads=[{"name": "tiny.chat", "config": "tiny",
                            "traffic": "chat", "chips": 1, "why": "test"}])
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
