#!/usr/bin/env python3
"""Read the numbers that a cell's correctness limit is set from, on the chip.

    python3 chipbench/readings.py --workload minitron4b.chat --seconds 12 \
        --seeds 11,12,13 [--program-control minitron4b-int8.chat]

For each seed, in one process: deploy, serve a short window of the cell's
mix, and compare a sample of what was served with the float32 reference
(``logit_gap``, the sound reading), and the same prompts and tokens with the
control, the reference in the next lower precision (``control_logit_gap``).
With ``--program-control`` the program's own lower-precision path is a
second control: that cell is served on the same seed and traffic and
compared with this cell's reference.  One JSON line per seed; with
``--dump`` the gap of every compared token too.
"""

import argparse
import json
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--program-control", default=None)
    p.add_argument("--dump", default=None,
                   help="directory for each seed's per-token gaps (.npz)")
    a = p.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU attached", file=sys.stderr)
        return 3
    from harness import check
    from harness.cell import Served
    from harness.spec import Bench
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = Bench(HERE.parent)
    log = lambda *x: print(*x, file=sys.stderr, flush=True)  # noqa: E731
    cfg = bench.config(bench.cell(a.workload)["config"])
    for seed in [int(s) for s in a.seeds.split(",")]:
        t0 = time.perf_counter()
        served = Served(bench, a.workload, seed, log=log)
        window, _, _ = served.serve(seed, a.seconds)
        finished = served.finished(window)
        served.close()
        t1 = time.perf_counter()
        got = check.compare(cfg, seed, served.traffic, finished,
                            control_bits=cfg["control_bits"], raw=True)
        raw = {"gaps": got.pop("gaps"), "control": got.pop("control_gaps")}
        got.update(seed=seed, control_bits=cfg["control_bits"],
                   reference_s=time.perf_counter() - t1)
        if a.program_control:
            other = Served(bench, a.program_control, seed, log=log)
            window, _, _ = other.serve(seed, a.seconds, served.traffic)
            finished = other.finished(window)
            other.close()
            ctl = check.compare(cfg, seed, served.traffic, finished,
                                raw=True)
            raw["program_control"] = ctl.pop("gaps")
            got.update({"program_control_" + k: ctl[k] for k in
                        ("tokens", "logit_gap", "mean_logit_gap",
                         "mean_sq_logit_gap", "argmax_share")})
        if a.dump:
            pathlib.Path(a.dump).mkdir(parents=True, exist_ok=True)
            np.savez(pathlib.Path(a.dump) / f"gaps.{seed}.npz", **raw)
        got["seconds"] = time.perf_counter() - t0
        print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
