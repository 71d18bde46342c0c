"""The reduction from trace events to busy time, per-op time and named
idle gaps."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce

DATA = Path(__file__).parent / "data"
DEV = "/device:TPU:0"

# A hand-made miniature, in microseconds x 1000: window 0..100 us.
HAND = {
    "ops": [[DEV, "fusion.1", 5000, 10000],      # 5..15
            [DEV, "fusion.2", 12000, 8000],      # 12..20 (overlaps)
            [DEV, "convolution.3", 40000, 30000],  # 40..70
            [DEV, "fusion.1", 95000, 20000]],    # 95..115, clipped at 100
    "modules": [[DEV, "jit__decode", 5000, 15000],
                [DEV, "jit__pf", 40000, 30000]],
    "host": [["python", "bench_window", 0, 100000],
             ["python", "decode_step", 18000, 30000],   # 18..48
             ["python", "admit", 60000, 40000],          # 60..100
             ["python", "prefill", 72000, 10000]],       # 72..82 inside admit
}


def test_hand_made_trace():
    r = trace_reduce.reduce(HAND)
    assert r["window_s"] == pytest.approx(100e-6)
    # busy: 5..20, 40..70, 95..100 = 15 + 30 + 5 us
    assert r["busy_s"] == pytest.approx(50e-6)
    assert r["ops"]["fusion.1"] == pytest.approx(15e-6)
    assert r["ops"]["fusion.2"] == pytest.approx(8e-6)
    assert r["modules"]["jit__pf"] == (pytest.approx(30e-6), 1)
    # gaps: 0..5, 20..40, 70..95 ; longest first
    gaps = r["top_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([25e-6, 20e-6, 5e-6])
    # 70..95: admit covers 25 us, prefill (inside it) covers 10: admit
    # 20..40: decode_step covers all 20 us
    # 0..5: nothing on the host
    assert [g[0] for g in gaps] == ["admit", "decode_step", "no host event"]


def test_program_spans_name_gaps_and_innermost_wins():
    extra = [["sched", "swap_apply", 20000, 20000]]   # 20..40, shorter
    r = trace_reduce.reduce(HAND, extra_host=extra)
    assert r["top_gaps"][1][0] == "swap_apply"


def _brute(events):
    lo, hi = trace_reduce.window_of(events, "bench_window")
    step = 1000  # 1 us bins
    t = np.arange(lo, hi, step) + step / 2
    busy = np.zeros(len(t), bool)
    for _, _, s, d in events["ops"]:
        busy |= (t >= s) & (t < s + d)
    return busy.sum() * step / 1e9, (hi - lo) / 1e9


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("recorded_*.json")))
def test_recorded_trace_matches_a_brute_force_count(name):
    events = json.loads((DATA / name).read_text())
    r = trace_reduce.reduce(events)
    busy, window = _brute(events)
    assert r["window_s"] == pytest.approx(window)
    assert r["busy_s"] == pytest.approx(busy, abs=2e-6 * len(events["ops"]) + 1e-6)
    total = sum(r["ops"].values())
    assert total >= r["busy_s"] - 1e-9   # overlapping ops count in both
    idle = r["window_s"] - r["busy_s"]
    assert sum(g[1] for g in r["top_gaps"]) <= idle + 1e-9
    assert len(r["top_gaps"]) <= 10 and len(r["top_ops"]) <= 10
