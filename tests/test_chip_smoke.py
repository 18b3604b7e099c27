"""``chip_smoke.py`` rehearsed on the CPU at smoke size.

The script's own ``main()`` refuses to run without a TPU, so these tests
drive its phases directly: the same deploy -> serve -> teacher-forced check
path, with the smoke cut of minitron-4b and the Pallas kernel interpreted.
"""

import importlib.util
import json
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.engines import CompiledEngine
from repro.models import random_checkpoint

SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
ARCH = "minitron-4b-smoke"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return random_checkpoint(get_arch(ARCH), seed=0)


@pytest.mark.parametrize("fmt", ["rsm", "rsm_int8"])
def test_serve_phase_serves_and_checks(chip_smoke, params, fmt):
    res = chip_smoke.serve_phase(ARCH, fmt, params)
    json.dumps(res)                            # one JSON line per phase
    assert res["phase"] == fmt
    assert res["tf_max_rel_gap"] <= res["tf_tol"]
    assert res["tf_argmax_share"] > 0.9
    assert res["compiles_warm"] == 0           # the warm window compiles none
    assert res["ttft_p50_s"] > 0 and res["itl_p50_s"] > 0
    assert res["output_tok_s"] > 0
    # interpreted on the CPU: the kernel is plain XLA, not a TPU custom call
    assert res["tpu_custom_call"] is False


def test_teacher_forced_flags_a_wrong_token(chip_smoke, params):
    cfg = get_arch(ARCH)
    prompts = np.arange(2 * 8, dtype=np.int32).reshape(2, 8)
    tokens = CompiledEngine(cfg, params, 32).generate(prompts, 4).tokens
    good = chip_smoke.teacher_forced(cfg, params, prompts, tokens)
    assert good["tf_max_rel_gap"] <= chip_smoke.TF_TOL
    tokens[0, 2] = (tokens[0, 2] + 1) % cfg.vocab_size
    bad = chip_smoke.teacher_forced(cfg, params, prompts, tokens)
    assert bad["tf_max_rel_gap"] > chip_smoke.TF_TOL


def test_main_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == ""                           # no result line
    assert "no TPU attached" in err


def test_chip_spec_is_keyed_by_device_kind():
    from repro.energy.hw import TPU_V5E, chip_spec

    assert chip_spec("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="no ChipSpec"):
        chip_spec("TPU v9 imaginary")


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache sits at a fixed, git-ignored path inside the checkout."""
    import jax

    from repro.launch.compile_cache import CHECKOUT_CACHE, use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env_dir))
    try:
        used = use_compile_cache()
        if env_dir is None:
            assert used == str(CHECKOUT_CACHE)
            assert jax.config.jax_compilation_cache_dir == used
            root = SCRIPT.parent
            assert CHECKOUT_CACHE.parent == root
            assert ".jax_cache/" in (root / ".gitignore").read_text().split()
        else:
            assert used == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_script_alone_fails(tmp_path):
    """Copied without the rest of the repo, the script fails and prints no
    result."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
