#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, the program's objects, warm-up of every
shape the cell's traffic uses), then the measured window, then the check
against the plain reference.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` and, last, ``checks`` (each number compared, with its limit).
The compared numbers are also the last lines of standard error.

It refuses to run, printing no result, without a TPU holding as many
chips as the cell asks for, and outside a checkout that holds the
program's sources under ``src/``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str, code: int) -> None:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no program sources under {ROOT / 'src'}", 2)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, layouts
    try:
        cell = harness.find_cell(args.workload, seed=args.seed,
                                 seconds=args.seconds, trace=bool(args.trace))
    except (KeyError, FileNotFoundError, layouts.ConfigError) as e:
        fail(str(e), 2)

    t_start = time.perf_counter()
    jax = harness.init_jax()
    devs = jax.devices()
    chips = int(cell.workload["chips"])
    if devs[0].platform != "tpu":
        fail(f"needs a TPU; JAX found {devs[0].platform}", 3)
    if len(devs) < chips:
        fail(f"cell {cell.name} needs {chips} chips; JAX found {len(devs)}", 3)
    from bench import peaks
    try:
        peaks.for_device(devs[0].device_kind)
    except KeyError as e:
        fail(str(e), 3)

    harness.stage("jax up", t_start)
    out = harness.driver(cell.traffic["kind"]).run(cell, t_start=t_start)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out.memory_peak_bytes}
    tr = out.ctx.get("trace")
    if cell.trace and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
    line = harness.result_line(cell, out, device)
    harness.print_checks(out.checks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
