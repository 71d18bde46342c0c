"""From the profiler's ``.xplane.pb`` of one measured window to numbers.

``load`` flattens the trace into plain events: each device operation
(the ``XLA Ops`` line of every ``/device:`` plane), each device program
(``XLA Modules``), and each host event (every line of ``/host:CPU``,
including the benchmark's ``bench_window`` annotation).  ``reduce`` works
on those events alone, so a small recorded trace kept as JSON checks it.

- busy: the union of the intervals in which an operation runs on a
  device, inside the window, averaged over the devices;
- per-operation and per-program device time inside the window;
- idle gaps: the stretches of the window with no operation running,
  each named after the host event that covers most of it (the innermost
  when several do), ``"no host event"`` when none does.
"""
from __future__ import annotations

import json
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

Interval = Tuple[int, int]


def load(path) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = {"ops": [], "modules": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                kind = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if kind:
                    # an op's name is its HLO text: keep the part before " = "
                    out[kind] += [[plane.name, ev.name.split(" = ")[0],
                                   int(ev.start_ns), int(ev.duration_ns)]
                                  for ev in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"] += [[line.name, ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)] for ev in line.events]
    return out


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def window_of(events: dict, name: str) -> Interval:
    wins = [(s, s + d) for _, n, s, d in events["host"] if n == name]
    if not wins:
        raise ValueError(f"no host event {name!r} in the trace")
    return max(wins, key=lambda w: w[1] - w[0])


def reduce(events: dict, window_name: str = "bench_window", top: int = 10,
           extra_host: List[list] = ()) -> dict:
    """``extra_host``: more host spans ``[lane, name, start_ns, dur_ns]``
    on the trace's clock (the program's own spans, mapped there)."""
    lo, hi = window_of(events, window_name)
    devices = sorted({p for p, *_ in events["ops"]}) or ["none"]
    busy_ns = 0
    gaps: List[Interval] = []
    for dev in devices:
        iv = [_clip(s, s + d, lo, hi) for p, _, s, d in events["ops"] if p == dev]
        u = _union([(a, b) for a, b in iv if b > a])
        busy_ns += sum(b - a for a, b in u)
        if dev == devices[0]:
            edges = [lo] + [x for ab in u for x in ab] + [hi]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    ops: Dict[str, float] = defaultdict(float)
    for _, n, s, d in events["ops"]:
        a, b = _clip(s, s + d, lo, hi)
        if b > a:
            ops[n] += (b - a) / 1e9
    modules: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for _, n, s, d in events["modules"]:
        a, b = _clip(s, s + d, lo, hi)
        if b > a:
            modules[n][0] += (b - a) / 1e9
            modules[n][1] += 1
    host = [(n, s, s + d) for _, n, s, d in list(events["host"]) + list(extra_host)
            if n != window_name and d > 0]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover, best_len = "no host event", 0, float("inf")
        for n, s, e in host:
            c = min(b, e) - max(a, s)
            # most cover wins; among equal cover, the shorter (innermost)
            if c > cover or (c == cover and c > 0 and e - s < best_len):
                best, cover, best_len = n, c, e - s
        named.append([best, (b - a) / 1e9])
    n_dev = len(devices)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "ops": dict(ops),
        "modules": {k: tuple(v) for k, v in modules.items()},
        "top_ops": sorted(([k, v] for k, v in ops.items()),
                          key=lambda kv: -kv[1])[:top],
        "top_gaps": named,
        "window_start_ns": lo,
    }


def reduce_file(path, window_name: str = "bench_window", **kw) -> dict:
    return reduce(load(path), window_name, **kw)


def save_events(events: dict, path) -> None:
    Path(path).write_text(json.dumps(events))
