#!/usr/bin/env python3
"""Serve one benchmark cell on the chip, on the wall clock, and check it.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  The cell (``BENCHMARK.json``) names a configuration, whose file
says what to serve, and a traffic mix.  The run makes the weights from the
seed, deploys them through ``ServingSpec`` -> ``ServingSession.deploy``, warms
the cell's shapes, serves the mix for ``--seconds`` on the wall clock, and
compares a sample of what it served with a float32 reference.  With
``--trace 1`` it traces a slice of the window and reports the per-layer
metrics instead of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, and with a trace
``breakdown``; ``checks`` last, each number compared beside its limit).
The checks are also the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits
non-zero.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
SRC = ROOT / "src"
TRACE_DIR = ROOT / ".chipbench" / "trace"


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def fail(msg: str, code: int) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    args = parse(argv)
    if not (SRC / "repro").is_dir():
        return fail(f"no program under {SRC.name}/ in this checkout", 2)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(SRC))
    import jax

    from harness.peaks import peaks
    from harness.spec import Bench

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"no TPU attached (JAX found {devs[0].platform!r}); "
                    "the benchmark has no CPU path", 3)
    if len(devs) < cell["chips"]:
        return fail(f"{args.workload} needs {cell['chips']} chips, "
                    f"found {len(devs)}", 3)
    pk = peaks(devs[0].device_kind)

    from repro.launch.compile_cache import use_compile_cache
    from harness.cell import run_cell

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start=T_START, peaks=pk,
                   trace_dir=str(TRACE_DIR),
                   log=lambda *a: print(*a, file=sys.stderr))
    out.pop("record")
    shutil.rmtree(TRACE_DIR, ignore_errors=True)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": out.pop("memory_peak_bytes")}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": device, "info": out["info"]}
    traced = out.get("trace")
    if traced is not None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        line["breakdown"] = traced["breakdown"]
    line["checks"] = out["checks"]
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
