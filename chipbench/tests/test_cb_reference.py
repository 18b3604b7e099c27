"""The float32 reference against the program at a small size, its weights,
and the control in a lower precision."""

import chipbench_fixtures as fx
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import check, weights as W
from harness.reference import logit_gaps, quantize

SEED = 2**32 + 3


def test_a_layer_made_alone_equals_that_layer_of_the_tree():
    tree = W.make_params(fx.TINY, SEED, "bfloat16")
    for l in range(fx.TINY["num_hidden_layers"]):
        one = W.layer_params(fx.TINY, SEED, l, "bfloat16")
        for path, leaf in jax.tree_util.tree_flatten_with_path(one)[0]:
            full = tree["layers"]
            for p in path:
                full = full[p.key]
            assert jnp.array_equal(full[l], leaf), path
    assert jnp.array_equal(tree["lm_head"],
                           W.table(fx.TINY, SEED, "lm_head", "bfloat16"))
    other = W.make_params(fx.TINY, SEED + 2**32, "bfloat16")
    assert not jnp.array_equal(tree["embed"], other["embed"])


def test_the_weights_fit_the_tree_the_program_loads():
    from harness.cell import program_config
    from repro.models.transformer import init_params

    pcfg = program_config(fx.TINY)
    want = jax.eval_shape(lambda: init_params(pcfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: W.make_params(fx.TINY, 1, "float32"))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(got)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(want)]


def _program_gaps(seqs, rows, served, params):
    from harness.cell import program_config
    from repro.models import forward

    logits = forward(params, program_config(fx.TINY),
                     {"tokens": jnp.asarray(seqs)})["logits"]
    lr = np.asarray(logits)[rows[:, 0], rows[:, 1]]
    return lr.max(-1) - lr[np.arange(len(served)), served]


def _gaps(seqs, rows, served, **kw):
    """The reference's gaps with each served token at its (row, position)."""
    grid = np.full(seqs.shape, -1, np.int32)
    grid[rows[:, 0], rows[:, 1]] = served
    return logit_gaps(fx.TINY, SEED, [(seqs, grid)], **kw)


def test_reference_logits_agree_with_the_program():
    rng = np.random.default_rng(0)
    seqs = rng.integers(0, 1024, (3, 40)).astype(np.int32)
    rows = np.array([(i, p) for i in range(3) for p in range(0, 40, 3)],
                    np.int32)
    params = W.make_params(fx.TINY, SEED, "float32")
    # the program's own choice at each row, then a random token
    from harness.cell import program_config
    from repro.models import forward
    logits = np.asarray(forward(params, program_config(fx.TINY),
                                {"tokens": jnp.asarray(seqs)})["logits"])
    best = logits[rows[:, 0], rows[:, 1]].argmax(-1).astype(np.int32)
    rand = rng.integers(0, 1024, len(rows)).astype(np.int32)
    for served in (best, rand):
        ref, _ = _gaps(seqs, rows, served)
        prog = _program_gaps(seqs, rows, served, params)
        np.testing.assert_allclose(ref, prog, atol=2e-4)
    ref_best, _ = _gaps(seqs, rows, best)
    assert ref_best.max() < 1e-4
    assert ref_rand_gap(seqs, rows, rand) > 0.5


def ref_rand_gap(seqs, rows, rand):
    return _gaps(seqs, rows, rand)[0].mean()


def test_quantize_is_per_output_channel_and_symmetric():
    w = jnp.array([[1.0, -0.5], [-2.0, 0.25], [0.5, 0.125]])
    q = quantize(w, 8)
    step = 2.0 / 127                       # column 0: |max| 2 over 127 steps
    np.testing.assert_allclose(q[:, 0], [64 * step, -2.0, 32 * step],
                               rtol=1e-6)
    assert float(jnp.abs(q[:, 0]).max()) == pytest.approx(2.0)
    assert float(jnp.abs(q[:, 1]).max()) == pytest.approx(0.5)
    assert len(np.unique(np.asarray(quantize(w, 2)[:, 1]))) <= 3


def test_the_control_in_a_lower_precision_fails_the_limit():
    """The control (the reference with its layer matrices in int4) put in the
    program's place reads far above what the program reads."""
    rng = np.random.default_rng(1)
    finished = []
    from harness.cell import program_config
    from repro.models import forward
    params = W.make_params(fx.TINY, SEED, "float32")
    for rid in range(4):
        prompt = rng.integers(0, 1024, 16).astype(np.int32)
        seq = jnp.asarray(prompt)[None]
        toks = []
        for _ in range(10):           # greedy, by the program's forward
            lg = forward(params, program_config(fx.TINY),
                         {"tokens": seq})["logits"][0, -1]
            t = int(jnp.argmax(lg))
            toks.append(t)
            seq = jnp.concatenate([seq, jnp.array([[t]], jnp.int32)], 1)
        finished.append((rid, prompt, np.array(toks, np.int32)))
    traffic = dict(fx.CHAT, check_sample=4)
    got = check.compare(fx.TINY, SEED, traffic, finished, control_bits=4)
    assert got["logit_gap"] < 1e-4
    assert got["control_logit_gap"] > 1e-3


def test_requests_are_checked_in_blocks_sorted_by_length():
    rng = np.random.default_rng(2)
    chosen = [(rid, rng.integers(0, 1024, P).astype(np.int32),
               rng.integers(0, 1024, m).astype(np.int32))
              for rid, (P, m) in enumerate([(64, 300), (8, 3), (512, 16),
                                             (16, 12), (32, 200), (1024, 512),
                                             (8, 8)])]
    got = check.blocks(chosen)
    assert [s.shape for s, _ in got] == [(check.BLOCK, 256),
                                         (check.BLOCK, 1536)]
    served = np.concatenate([g[g >= 0] for _, g in got])
    assert len(served) == sum(len(t) for _, _, t in chosen)
    by_len = sorted(chosen, key=lambda f: len(f[1]) + len(f[2]))
    for k, (rid, prompt, toks) in enumerate(by_len):
        seqs, grid = got[k // check.BLOCK]
        row = k % check.BLOCK
        P, m = len(prompt), len(toks)
        assert (seqs[row, :P] == prompt).all()
        assert (seqs[row, P:P + m - 1] == toks[:-1]).all()
        assert (grid[row, P - 1:P - 1 + m] == toks).all()
        assert (grid[row] >= 0).sum() == m
    # the rows that pad the last block hold no served token
    assert (got[-1][1][len(chosen) % check.BLOCK:] == -1).all()
