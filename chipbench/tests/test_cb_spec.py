"""The harness finds a cell's parts by name, and refuses to run off the
chip."""

import json
import os
import shutil
import subprocess
import sys

import chipbench_fixtures as fx
import pytest

from harness.spec import Bench


def test_new_config_traffic_and_metric_files_are_found_by_name(tmp_path):
    root = fx.make_checkout(tmp_path)
    cb = root / "chipbench"
    (cb / "configs" / "tiny2.json").write_text(json.dumps(
        dict(fx.TINY, name="tiny2")))
    (cb / "traffic" / "burst.json").write_text(json.dumps(
        dict(fx.CHAT, slots=2)))
    (cb / "limits" / "tiny2.burst.json").write_text(json.dumps(
        {"logit_gap": {"limit": 0.5}}))
    (cb / "metrics" / "slots_seen.py").write_text(
        "def read(run):\n    return run.traffic['slots']\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny2", "source": "test",
                            "file": "chipbench/configs/tiny2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny2.burst", "config": "tiny2",
                              "traffic": "burst", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "slots_seen", "unit": "slots",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "itl_p95_s",
                              "workloads": ["tiny2.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    b = Bench(root)
    cell = b.cell("tiny2.burst")
    assert b.config(cell["config"])["name"] == "tiny2"
    assert b.traffic(cell["traffic"])["slots"] == 2
    assert b.limits("tiny2.burst")["logit_gap"]["limit"] == 0.5
    names = [m["name"] for m in b.metrics("tiny2.burst", trace=True)]
    assert "slots_seen" in names and "deploy_s" in names
    assert "queue_wait_p50_s" not in names      # listed for other cells
    assert "slots_seen" not in [m["name"] for m in
                                b.metrics("tiny.chat", trace=True)]

    class FakeRun:
        traffic = {"slots": 2}
    assert b.reader("slots_seen").read(FakeRun()) == 2
    with pytest.raises(FileNotFoundError):
        b.reader("no_such_metric")


def test_every_metric_the_benchmark_names_has_a_reader():
    b = Bench(fx.ROOT)
    for group in ("end_to_end", "per_layer"):
        for m in b.spec[group]:
            assert callable(b.reader(m["name"]).read), m["name"]
    for w in b.spec["workloads"]:
        b.config(w["config"])
        b.traffic(w["traffic"])
        limits = b.limits(w["name"])
        assert limits and set(limits) <= {"logit_gap", "mean_logit_gap"}
        assert all(v["limit"] > 0 for v in limits.values())


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "minitron4b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_without_a_tpu_it_prints_no_result_and_fails():
    p = _run(fx.ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_without_the_program_it_prints_no_result_and_fails(tmp_path):
    shutil.copy(fx.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(fx.CB, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
