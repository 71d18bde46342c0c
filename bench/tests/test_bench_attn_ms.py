"""``attn_ms.train``: device ms a window step of the ops under the
``flash_attn`` scope, found bare or inside a transform's name."""
import json
from pathlib import Path

import pytest

from bench import harness

DATA = Path(__file__).parent / "data"

DEV = "/device:TPU:0"
BODY = "jit(step)/blockllm_grad/jvp()/while/body/closed_call"
BWD = "jit(step)/blockllm_grad/transpose(jvp())/while/body/closed_call"
FWD_K = BODY + ("/flash_attn/vmap(jit(_splash_attention))/"
                "splash_mha_fwd_residuals/pallas_call")

# A hand-made miniature in ns, window 0..100: the forward kernel and its
# layout copy carry the scope as a component; the backward's kernels
# under remat carry it too, one of them with the scope wrapped by the
# transforms; a scope whose name only starts alike does not count.
HAND = {
    "window": [0, 100],
    "ops": [[DEV, "%while.19", "", -10, 40],
            [DEV, "%fusion.1", BODY + "/dot_general", 0, 10],
            [DEV, "%splash_mha_fwd_residuals.1", FWD_K, 10, 8],      # 10..18
            [DEV, "%fusion.2", BODY + "/flash_attn/transpose", 16, 4],   # ..20
            [DEV, "%fusion.3", BODY + "/flash_attn_x/mul", 20, 5],
            [DEV, "%while.18", "", 40, 60],
            [DEV, "%splash_mha_dq.1", BWD + "/checkpoint/flash_attn/"
             "vmap(jit(_splash_attention))/splash_mha_dq_no_residuals/"
             "pallas_call", 50, 6],                                   # 50..56
            [DEV, "%splash_mha_dkv.1", BWD + "/transpose(jvp(flash_attn))/"
             "splash_mha_dkv_no_residuals/pallas_call", 56, 9],       # 56..65
            [DEV, "%fusion.4", BWD + "/checkpoint/dot_general", 65, 20],
            [DEV, "%fusion.5", BODY + "/flash_attn/mul", 95, 10]],    # ..100
}


def _read(events, steps=2):
    return harness.read_metric("attn_ms.train",
                               {"attn_events": events, "steps": steps})


def test_hand_made_union_per_step():
    # 10..20 (kernel and copy overlap), 50..65, 95..100: 30 ns over 2 steps
    assert _read(HAND) == pytest.approx(30e-9 * 1e3 / 2)


def test_null_where_no_op_carries_the_scope():
    bare = {"window": HAND["window"],
            "ops": [o for o in HAND["ops"]
                    if "flash_attn/" not in o[2]
                    and "(flash_attn)" not in o[2]]}
    assert bare["ops"] and _read(bare) is None
    assert _read(HAND, steps=0) is None
    # an untraced run: no trace of the window at all
    cell = harness.Cell("no-such-cell", {}, {}, {}, {}, {}, 1, 1.0, False)
    assert harness.read_metric("attn_ms.train",
                               {"cell": cell, "steps": 4}) is None


def _union(iv):
    total, end = 0, None
    for a, b in sorted(iv):
        if end is None or a > end:
            total, end = total + b - a, b
        elif b > end:
            total, end = total + b - end, b
    return total


def test_recorded_chip_excerpt():
    """An excerpt of a TPU v5e trace of the fine-tune with the flash
    kernel: the start of the forward scan and one layer of the backward
    scan, whose dk/dv and dq kernels sit under ``transpose(jvp())``."""
    events = json.loads((DATA / "attn_ft.json").read_text())
    lo, hi = events["window"]
    flash = [(max(s, lo), min(s + d, hi)) for _, n, p, s, d in events["ops"]
             if "flash_attn" in p.split("/")]
    names = [n for _, n, p, *_ in events["ops"] if "flash_attn" in p]
    assert any(n.startswith("%splash_mha_dkv") for n in names)
    assert any(n.startswith("%splash_mha_dq") for n in names)
    assert all("transpose(jvp())" in p for _, n, p, *_ in events["ops"]
               if n.startswith("%splash"))
    assert _read(events, steps=3) == pytest.approx(
        1e3 * _union(flash) / 1e9 / 3)
    # the same ops from a program without the scope read nothing
    bare = {"window": events["window"],
            "ops": [[*o[:2], o[2].replace("flash_attn", "attention"), *o[3:]]
                    for o in events["ops"]]}
    assert _read(bare) is None
