"""A configuration brings its architecture as a module of its own: the dense
decoder's bits pinned, a second real architecture and a toy with layers of
two kinds added to a checkout as new files, no harness file edited."""

import hashlib
import shutil

import chipbench_fixtures as fx
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import arch, ops, weights as W
from harness.reference import logit_gaps
from harness.spec import Bench

SEED = 2**32 + 3
DATA = fx.CB / "tests" / "data"


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def _fixed_block():
    rng = np.random.default_rng(7)
    seqs = rng.integers(0, 1024, (4, 48)).astype(np.int32)
    served = np.full(seqs.shape, -1, np.int32)
    served[:, 8:] = np.concatenate(
        [seqs[:, 9:], rng.integers(0, 1024, (4, 1))], 1)
    return [(seqs, served)]


# recorded before the dense decoder moved into its module: the seed gives
# the same weights and the reference the same gaps, bit for bit
PINNED_PARAMS = {
    "bfloat16":
        "57e67a09c130fa8b8a88a98a239b2b27aab6dc05a19419af65812d90a377757c",
    "float32":
        "b082b6694c4d67454e61ff6a3d68fd5e69f332597b4532d093e23559437def1f",
}
PINNED_GAPS = {
    "tiny": (
        "ad17149023b3de1ed6d62d7e45d30b251c03c12ac0510f449bdc093ee387f2d7",
        "fa145e792a8ec283797f4c58ae3d94a536e1923cce39642e3f12f4d82750f151"),
    "tiny-int8": (
        "7c95d097686a1c6591cbda784e880490b383b01736fc70a9568867c6c8948d6c",
        "9f94f94bcefde10af8c8d764ba72f5969e75dc15db0946a55c27713597ecf468"),
}


@pytest.mark.parametrize("dtype", sorted(PINNED_PARAMS))
def test_the_dense_module_makes_the_pinned_weights(dtype):
    tree = W.make_params(fx.TINY, SEED, dtype)
    assert _digest(jax.tree.leaves(tree)) == PINNED_PARAMS[dtype]


@pytest.mark.parametrize("cfg", [fx.TINY, fx.TINY_INT8],
                         ids=lambda c: c["name"])
def test_the_dense_module_gives_the_pinned_reference_gaps(cfg):
    gaps, ctl = logit_gaps(cfg, SEED, _fixed_block(),
                           control_bits=cfg["control_bits"])
    assert (_digest([gaps]), _digest([ctl])) == PINNED_GAPS[cfg["name"]]


def test_a_configuration_without_an_architecture_is_an_error():
    cfg = {k: v for k, v in fx.TINY.items() if k != "architecture"}
    with pytest.raises(KeyError, match="architecture"):
        W.make_params(cfg, SEED, "float32")
    with pytest.raises(FileNotFoundError):
        arch.of(dict(fx.TINY, architecture="no_such_arch"))


def test_no_harness_file_names_an_architecture():
    for path in sorted((fx.CB / "harness").glob("*.py")):
        text = path.read_text()
        for word in ('"family"', "relu", "wq"):
            assert word not in text, (path.name, word)


def _checkout(tmp_path, module: str, config: dict):
    """A checkout with one more architecture module, as new files only."""
    root = fx.make_checkout(tmp_path, config=config)
    shutil.copy(DATA / f"{module}.py", root / "chipbench" / "arch")
    assert not (fx.CB / "arch" / f"{module}.py").exists()
    return Bench(root)


# yi-9b's smoke cut (llama-style, SwiGLU MLP), as the program defines it
YI_TINY = {
    "name": "yi-tiny", "model_type": "llama", "architecture": "dense_swiglu",
    "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 32, "num_hidden_layers": 2,
    "vocab_size": 1024, "hidden_act": "silu", "norm_eps": 1e-05,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "rope_theta": 1000000.0, "rotary_fraction": 1.0, "weights": "float32",
    "control_bits": 8,
    "serving": {"arch": "yi-9b-smoke", "format": "rsm"},
}


def test_a_swiglu_decoder_is_one_new_file(tmp_path):
    from harness.cell import program_config
    from repro.models import forward
    from repro.models.transformer import init_params

    bench = _checkout(tmp_path, "dense_swiglu", YI_TINY)
    cfg = bench.config("tiny")
    assert arch.of(cfg).__file__.startswith(str(tmp_path))

    # its weights fit the tree the program builds for yi-9b-smoke
    pcfg = program_config(cfg)
    want = jax.eval_shape(lambda: init_params(pcfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: W.make_params(cfg, 1, "float32"))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(got)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(want)]
    assert set(got["layers"]["mlp"]) == {"wi_gate", "wi_up", "wo"}

    # its reference agrees with the program's forward on seeded weights
    rng = np.random.default_rng(3)
    seqs = rng.integers(0, 1024, (3, 40)).astype(np.int32)
    rows = np.array([(i, p) for i in range(3) for p in range(0, 40, 3)],
                    np.int32)
    params = W.make_params(cfg, SEED, "float32")
    lg = np.asarray(forward(params, pcfg,
                            {"tokens": jnp.asarray(seqs)})["logits"])
    lr = lg[rows[:, 0], rows[:, 1]]
    best = lr.argmax(-1).astype(np.int32)
    rand = rng.integers(0, 1024, len(rows)).astype(np.int32)
    for served in (best, rand):
        grid = np.full(seqs.shape, -1, np.int32)
        grid[rows[:, 0], rows[:, 1]] = served
        ref, _ = logit_gaps(cfg, SEED, [(seqs, grid)])
        prog = lr.max(-1) - lr[np.arange(len(served)), served]
        np.testing.assert_allclose(ref, prog, atol=2e-4)
    assert ref.mean() > 0.5


def test_the_swiglu_counts_by_hand_at_yi_9b_widths(tmp_path):
    bench = _checkout(tmp_path, "dense_swiglu", dict(
        YI_TINY, hidden_size=4096, intermediate_size=11008,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        num_hidden_layers=48, vocab_size=64000))
    cfg = bench.config("tiny")
    D, F, L, V, Q, KV = 4096, 11008, 48, 64000, 32 * 128, 4 * 128
    mats = D * Q + 2 * D * KV + Q * D + 3 * D * F        # 173,015,040
    assert mats == 173_015_040
    assert ops.total_params(cfg) == L * (mats + 2 * D) + 2 * V * D + D \
        == 8_829_407_232                                  # yi-9b: 8.83B
    bf16 = 2 * (L * (mats + 2 * D) + D * V + D)
    assert ops.served_weight_bytes(cfg, "bfloat16") == bf16
    assert ops.served_weight_bytes(cfg, "bfloat16", True) == bf16 + 2 * D * V
    cols = Q + 2 * KV + D + 2 * F + D
    assert ops.served_weight_bytes(cfg, "int8") == \
        L * (mats + 4 * cols + 4 * D) + 2 * D * V + 2 * D
    lengths = [99, 9]
    assert ops.decode_flops(cfg, lengths) == \
        2 * 2 * (L * mats + D * V) + 4 * L * Q * (100 + 10)
    assert ops.decode_bytes(cfg, lengths, "bfloat16") == \
        bf16 + 110 * 2 * L * KV * 2
    S = 2048
    assert ops.prefill_flops(cfg, S) == \
        2 * L * mats * S + 4 * L * Q * S * (S + 1) / 2 + 2 * D * V
    calls = ops.int8_calls(cfg, 8)
    assert len(calls) == 7 * L
    assert calls[4:7] == [(8, D, F), (8, D, F), (8, F, D)]


TWO_KINDS = {
    "name": "two-kinds", "architecture": "two_kinds", "hidden_size": 64,
    "vocab_size": 96, "num_hidden_layers": 4, "b_layers": [2],
    "norm_eps": 1e-05, "torch_dtype": "float32", "weights": "float32",
}


def test_layers_of_two_kinds_stacked_over_their_own_counts(tmp_path):
    bench = _checkout(tmp_path, "two_kinds", TWO_KINDS)
    cfg = bench.config("tiny")
    tree = W.make_params(cfg, SEED, "float32")
    assert tree["a_layers"]["w"].shape == (3, 64, 64)
    assert tree["b_layers"]["u"].shape == (1, 64, 64)
    assert tree["norms"]["norm"].shape == (4, 64)

    # each layer made alone equals its slice of each stack it reads
    at = {"a_layers": [0, 1, None, 2], "b_layers": [None, None, 0, None],
          "norms": [0, 1, 2, 3]}
    for l in range(4):
        one = W.layer_params(cfg, SEED, l, "float32")
        groups = [g for g in at if at[g][l] is not None]
        assert sorted(one) == sorted(k for g in groups for k in tree[g])
        for g in groups:
            for name, leaf in tree[g].items():
                assert jnp.array_equal(leaf[at[g][l]], one[name]), (l, g)

    # the reference picks each layer's function by its index: a, a, b, a
    rng = np.random.default_rng(4)
    seqs = rng.integers(0, 96, (2, 12)).astype(np.int32)
    served = rng.integers(0, 96, seqs.shape).astype(np.int32)
    gaps, ctl = logit_gaps(cfg, SEED, [(seqs, served)], control_bits=4)
    assert gaps.shape == ctl.shape == (24,)

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + 1e-5) * g

    hi = jax.lax.Precision.HIGHEST
    h = tree["embed"][seqs]
    for l in range(4):
        x = norm(h, tree["norms"]["norm"][l])
        if l == 2:
            h = h + jnp.matmul(x, tree["b_layers"]["u"][0], precision=hi)
        else:
            i = at["a_layers"][l]
            h = h + jnp.tanh(jnp.matmul(x, tree["a_layers"]["w"][i],
                                        precision=hi)) * \
                tree["a_layers"]["g"][i]
    lg = jnp.matmul(norm(h, tree["final_norm"]), tree["embed"].T,
                    precision=hi).reshape(-1, 96)
    want = lg.max(-1) - lg[jnp.arange(24), served.reshape(-1)]
    np.testing.assert_allclose(gaps, np.asarray(want), atol=1e-4)
    assert gaps.max() > 0.1
