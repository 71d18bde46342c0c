#!/usr/bin/env python3
"""Readings that set a training cell's limits, made on the chip at the
cell's size; the benchmark's own runs never run this.

    python3 bench/controls.py --workload <cell> --seeds 11,12,... \\
        --control-seeds 3 [--look]

For every seed, in one process (set-up compiles once): the numbers the
cell compares, from the program driven as a run drives it (its lower
reading), and the loss gap of every step.  For the first
``--control-seeds`` seeds also the control, the reference computed one
precision below the configuration's (float8 operands for bfloat16) put in
the program's place, and the fault of a step that leaves half of each
batch out, planted in the reference put in the program's place (a step
that returns its state unchanged reads 1 on ``change_gap`` by definition
and needs no run).

``--look`` also keeps the program's step-1 update mask and reports how
many of its elements differ from the reference's own, and the loss gap of
every step against the reference driven with the program's mask: what
the choice of near-threshold elements alone does to the later losses.

Each line of standard output is one JSON object per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _step_gaps(prog, ref):
    return [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]


def _look(cell, d, ref):
    """Mask elements that differ, and the reference on the program's mask."""
    import numpy as np
    from bench import layouts, train_cell
    tab = layouts.table(cell.config)
    pm = {p: v[np.argsort(d.rows[tab[p].stack])] if tab[p].stack else v
          for p, v in d.mask.items()}
    differ = {n: int((np.asarray(ref["mask"][n]) != pm[n]).sum())
              for n in pm}
    kept = {n: int(np.asarray(ref["mask"][n]).sum()) for n in pm}
    on_prog = train_cell.reference_numbers(cell, mask=pm)
    return {"mask_elements_differing": differ, "mask_elements_kept": kept,
            "step_loss_gaps_on_program_mask": _step_gaps(d.numbers(), on_prog),
            "losses_on_program_mask": on_prog["losses"]}


def readings(cell, control: bool, look: bool) -> dict:
    from bench import ref_blockllm, train_cell
    handle = train_cell.make_trainer(cell)
    d = train_cell.drive(cell, handle, train_cell.make_pipeline(cell),
                         t_start=time.perf_counter(), window=False,
                         keep_mask=look)
    del handle
    gc.collect()
    prog = d.numbers()
    ref = train_cell.reference_numbers(cell)
    out = {"program": ref_blockllm.gaps(prog, ref),
           "step_loss_gaps": _step_gaps(prog, ref),
           "losses": {"program": prog["losses"], "reference": ref["losses"]}}
    if look:
        out.update(_look(cell, d, ref))
    del ref["mask"], d
    gc.collect()
    if control:
        mod = __import__(f"bench.configs.{cell.config['reference']}",
                         fromlist=["fp8"])
        low = train_cell.reference_numbers(cell, mod.fp8)
        half = train_cell.reference_numbers(cell, half_batch=True)
        out["control_fp8"] = ref_blockllm.gaps(low, ref)
        out["fault_half_batch"] = ref_blockllm.gaps(half, ref)
        out["control_fp8"]["step_loss_gaps"] = _step_gaps(low, ref)
        out["fault_half_batch"]["step_loss_gaps"] = _step_gaps(half, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--look", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    harness.init_jax()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        cell = harness.find_cell(args.workload, seed=seed, seconds=0.0,
                                 trace=False)
        t0 = time.perf_counter()
        read = readings(cell, i < args.control_seeds, args.look)
        read.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(read), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
