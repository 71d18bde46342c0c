"""Device time by named scope (``bench/scopes.py``) and the three readers
of the train step's split: ``grad_ms.train``, ``update_ms.train`` and
``host_ms.train``."""
import json
import struct
import time
from pathlib import Path

import pytest

from bench import harness, scopes

DATA = Path(__file__).parent / "data"
DEV = "/device:TPU:0"
GRAD = "jit(step)/blockllm_grad/transpose(jvp())/while/body/closed_call"

# A hand-made miniature in ns, window 0..100, shaped like the chip's
# trace: the scan's ``while`` op carries no scope, its body's ops do, and
# XLA's own copies and hoisted converts carry none.
HAND = {
    "window": [0, 100],
    "ops": [[DEV, "%while.18", "", -10, 60],                 # 0..50
            [DEV, "%fusion.1", GRAD, 5, 15],                 # 5..20
            [DEV, "%fusion.2", GRAD, 15, 15],                # 15..30
            [DEV, "%copy-done.3", "", 32, 2],                # 32..34
            [DEV, "%fusion.4", "jit(step)/blockllm_grad_norms/reduce_sum:",
             52, 4],                                         # 52..56
            [DEV, "%fusion.5", "jit(step)/blockllm_update/sub:", 60, 20],
            [DEV, "%fusion.6", "jit(step)/blockllm_update/sub:", 70, 5],
            [DEV, "%convert.7", "", 85, 3],                  # 85..88
            [DEV, "%gather.8", "jit(gather)/gather:", 90, 20]],  # ..100
}


def test_hand_made_scopes():
    r = scopes.reduce(HAND)
    ns = pytest.approx
    assert r["window_s"] == ns(100e-9)
    assert r["busy_s"] == ns(87e-9)          # 50 + 4 + 20 + 3 + 10
    # fusion.1 and fusion.2 overlap by 5: their union is 5..30
    assert r["union_s"] == {"blockllm_grad": ns(25e-9),
                            "blockllm_grad_norms": ns(4e-9),
                            "blockllm_mask_refresh": 0.0,
                            "blockllm_update": ns(20e-9)}
    assert r["self_s"] == r["union_s"]
    # the while op's own time is what its body leaves: 0..5, 30..32, 34..50
    assert r["outside_s"] == {"%while.18": ns(23e-9), "%gather.8": ns(10e-9),
                              "%convert.7": ns(3e-9), "%copy-done.3": ns(2e-9)}
    assert sum(r["self_s"].values()) + sum(r["outside_s"].values()) \
        == ns(r["busy_s"])


def test_self_time_goes_to_the_innermost_op():
    # a scoped op around ops of another scope and of none: the union of
    # each scope keeps the overlap, the self times split it
    ev = {"window": [0, 100],
          "ops": [[DEV, "%while.1", GRAD, 0, 100],
                  [DEV, "%fusion.2", "jit(step)/blockllm_update/add:", 20, 30],
                  [DEV, "%copy.3", "", 60, 10]]}
    r = scopes.reduce(ev)
    assert r["union_s"]["blockllm_grad"] == pytest.approx(100e-9)
    assert r["union_s"]["blockllm_update"] == pytest.approx(30e-9)
    assert r["self_s"]["blockllm_grad"] == pytest.approx(60e-9)
    assert r["self_s"]["blockllm_update"] == pytest.approx(30e-9)
    assert r["outside_s"] == {"%copy.3": pytest.approx(10e-9)}


def _brute(events):
    """Busy time, each scope's union and self time, one elementary
    stretch between two op boundaries at a time."""
    lo, hi = events["window"]
    ops = [(max(s, lo), min(s + d, hi), s, scopes.scope_of(p))
           for _, _, p, s, d in events["ops"]]
    cuts = sorted({lo, hi} | {t for a, b, *_ in ops for t in (a, b)
                              if lo <= t <= hi})
    busy, union, self_ = 0, dict.fromkeys(scopes.SCOPES, 0), \
        dict.fromkeys(scopes.SCOPES, 0)
    for a, b in zip(cuts, cuts[1:]):
        on = [o for o in ops if o[0] <= a and b <= o[1]]
        if not on:
            continue
        busy += b - a
        for k in {o[3] for o in on} - {None}:
            union[k] += b - a
        inner = max(on, key=lambda o: (o[2], -o[1]))  # last started, shortest
        if inner[3] is not None:
            self_[inner[3]] += b - a
    return (busy / 1e9, {k: v / 1e9 for k, v in union.items()},
            {k: v / 1e9 for k, v in self_.items()})


def test_recorded_chip_excerpt_matches_a_brute_force_count():
    """An excerpt of a TPU v5e trace of the fine-tune: the end of the
    backward scan's ``while``, the gradient norms, the masked update, the
    eager probe gathers and the start of the next step."""
    events = json.loads((DATA / "scopes_ft.json").read_text())
    r = scopes.reduce(events)
    busy, union, self_ = _brute(events)
    assert r["busy_s"] == pytest.approx(busy)
    for k in scopes.SCOPES:
        assert r["union_s"][k] == pytest.approx(union[k]), k
        assert r["self_s"][k] == pytest.approx(self_[k]), k
    assert r["union_s"]["blockllm_grad"] > 0
    assert r["union_s"]["blockllm_update"] > r["union_s"]["blockllm_grad_norms"] > 0
    assert any(n.startswith("%while") for _, n, p, *_ in events["ops"] if not p)
    assert sum(r["self_s"].values()) + sum(r["outside_s"].values()) \
        == pytest.approx(r["busy_s"])


def _ctx(steps, **kw):
    return dict(kw, steps=steps)


def test_readers_arithmetic():
    r = scopes.reduce(HAND)
    ctx = _ctx(4, scopes=r)
    assert harness.read_metric("grad_ms.train", ctx) == pytest.approx(
        25e-9 * 1e3 / 4)
    assert harness.read_metric("update_ms.train", ctx) == pytest.approx(
        20e-9 * 1e3 / 4)
    ms = 1_000_000
    spans = [("data", 0, 3 * ms),
             ("train_step", 3 * ms, 103 * ms), ("plan", 3 * ms, 5 * ms),
             ("device_wait", 10 * ms, 90 * ms),
             ("train_step", 110 * ms, 150 * ms),
             ("device_wait", 111 * ms, 141 * ms),
             ("device_wait", 200 * ms, 300 * ms)]   # inside no train_step
    # own time: 100 - 80 and 40 - 30
    assert harness.read_metric("host_ms.train", {"spans": spans}) \
        == pytest.approx(15.0)


def test_readers_find_nothing_in_a_program_without_the_split(tmp_path):
    # a program with no scopes: its ops carry other paths
    bare = {"window": [0, 100],
            "ops": [[DEV, o[1], "jit(step)/" if o[2] else "", *o[3:]]
                    for o in HAND["ops"]]}
    ctx = _ctx(4, scopes=scopes.reduce(bare))
    assert harness.read_metric("grad_ms.train", ctx) is None
    assert harness.read_metric("update_ms.train", ctx) is None
    # no device_wait span: the host's time in the step is not split
    ms = 1_000_000
    ctx = {"spans": [("data", 0, ms), ("train_step", ms, 9 * ms)]}
    assert harness.read_metric("host_ms.train", ctx) is None
    # an untraced run: no trace of the window at all
    cell = harness.Cell("no-such-cell", {}, {}, {}, {}, {}, 1, 1.0, False)
    assert harness.read_metric("grad_ms.train", _ctx(4, cell=cell)) is None


@pytest.fixture(scope="module")
def profiled_window(tmp_path_factory):
    """A profiler trace made on the CPU: a ``bench_window`` annotation
    around a few jitted calls, each in a named scope."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("blockllm_update"):
            return jnp.sin(x) @ x

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tdir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(tdir))
    try:
        with jax.profiler.TraceAnnotation("bench_window"):
            t0 = time.monotonic_ns()
            for _ in range(3):
                f(x).block_until_ready()
            t1 = time.monotonic_ns()
    finally:
        jax.profiler.stop_trace()
    return next(tdir.rglob("*.xplane.pb")), t1 - t0


def test_loader_reads_the_window_from_a_profiler_file(profiled_window):
    from jax.profiler import ProfileData
    path, dur = profiled_window
    ev = scopes.load(path)
    pd = ProfileData.from_file(str(path))
    wins = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
            for p in pd.planes if p.name.startswith("/host:CPU")
            for line in p.lines for e in line.events
            if e.name == "bench_window"]
    assert [tuple(ev["window"])] == wins
    assert ev["window"][1] - ev["window"][0] >= dur
    # the CPU has no device plane: no op, and so no reading
    assert ev["ops"] == []
    assert scopes.ms_per_step({"scopes": scopes.reduce(ev), "steps": 3},
                              "blockllm_update") is None
    with pytest.raises(ValueError):
        scopes.load(path, window_name="no such window")


def _varint(x):
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _msg(*fields):
    """A protobuf message: ints as varints, str and bytes length-delimited,
    floats as fixed64."""
    out = b""
    for num, val in fields:
        if isinstance(val, float):
            out += _varint(num << 3 | 1) + struct.pack("<d", val)
        elif isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def _plane(name, lines, event_md, stat_md):
    fields = [(2, name)] + [(3, ln) for ln in lines]
    fields += [(4, _msg((1, k), (2, _msg((1, k), (2, n), *[(5, s) for s in st]))))
               for k, n, st in event_md]
    fields += [(5, _msg((1, k), (2, _msg((1, k), (2, n))))) for k, n in stat_md]
    return _msg(*fields)


def test_loader_reads_ops_and_their_scopes_from_a_device_plane(tmp_path):
    """An ``XSpace`` written field by field as the TPU profiler lays it
    out: the scope path on each op's event metadata as the ``tf_op``
    stat, events on the ``XLA Ops`` line by metadata id, offsets in ps."""
    tf_op, other = 7, 8
    ev = lambda mid, off_ps, dur_ps: _msg(          # noqa: E731
        (1, mid), (2, off_ps), (3, dur_ps),
        (4, _msg((1, other), (2, 1.5))))            # a double-valued stat
    device = _plane(
        "/device:TPU:0",
        [_msg((2, "XLA Modules"), (3, 1000), (4, ev(1, 0, 9_000))),
         _msg((2, "XLA Ops"), (3, 1000),
              (4, ev(1, 0, 5_000)),                # 1000..1005 ns
              (4, ev(2, 6_000, 2_000)),            # 1006..1008
              (4, ev(1, 90_000, 1_000))),          # 1090: outside
         ],
        [(1, "%fusion.1 = f32[2]{0} fusion(...)",
          [_msg((1, tf_op), (5, "jit(step)/blockllm_update/sub:"))]),
         (2, "%copy.2 = f32[2]{0} copy(...)",
          [_msg((1, other), (4, 12))])],
        [(tf_op, "tf_op"), (other, "flops")])
    host = _plane(
        "/host:CPU",
        [_msg((2, "python3"), (3, 990),
              (4, _msg((1, 5), (2, 0), (3, 40_000))),     # 990..1030
              (4, _msg((1, 6), (2, 2_000), (3, 1_000))))],
        [(5, "bench_window", []), (6, "train_step", [])], [])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host), (4, "host0")))
    assert scopes.load(path) == {
        "window": [990, 1030],
        "ops": [["/device:TPU:0", "%fusion.1",
                 "jit(step)/blockllm_update/sub:", 1000, 5],
                ["/device:TPU:0", "%copy.2", "", 1006, 2]]}
