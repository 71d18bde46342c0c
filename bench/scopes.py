"""Device time of the train step's named scopes inside the measured window.

The program names the phases of its train step with ``jax.named_scope``
(``core/blockllm.build_step_fn``): ``blockllm_grad`` (forward, loss and
backward), ``blockllm_grad_norms``, ``blockllm_mask_refresh`` and
``blockllm_update``.  XLA keeps the scope path in each op's metadata
(``op_name``), and the TPU profiler writes it on each ``XLA Ops`` event as
the stat ``tf_op``, e.g. ``jit(step)/blockllm_grad/transpose(jvp())/while``.

The stat sits on the op's event metadata, which ``jax.profiler.ProfileData``
does not show, so ``load`` reads the window's ``.xplane.pb`` (the one
``harness.window`` wrote under ``harness.RUNS / "trace" / <cell>``) with a
small protobuf reader of its own (the ``XSpace`` message of
``tsl/profiler/protobuf/xplane.proto``).  It gives plain events: the
window's bounds and, for each device op, its name, scope path, start and
duration.  ``reduce`` works on those alone, so a small recorded excerpt
kept as JSON checks it.  Inside the window, averaged over the devices:

- ``union_s``: per scope, the union of the intervals of its ops (an op
  is under a scope when the scope's name is a component of its path).
  The scan's ``while`` op covers the ops of its body: the union counts
  that time once;
- ``self_s``: each instant of busy time goes to the innermost op running
  then (the one that started last), and so to its scope.  The self times
  of the scopes and of the ops outside every scope add up to busy time;
- ``outside_s``: the self time of the ops outside every scope, by name.
"""
from __future__ import annotations

import heapq
from collections import defaultdict
from pathlib import Path
from typing import Dict, Optional, Sequence

SCOPES = ("blockllm_grad", "blockllm_grad_norms", "blockllm_mask_refresh",
          "blockllm_update")


# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_MD_ID, _MD_NAME, _MD_STATS = 1, 2, 5        # XEventMetadata, XStatMetadata
_STAT_MD_ID, _STAT_STR = 1, 5
_MAP_VALUE = 2


def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b, i: int, end: int):
    """``(number, value)`` of each field of the message in ``b[i:end]``;
    a length-delimited value comes as its ``(start, end)`` in ``b``."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _str(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _plane(b, span):
    """A plane's name, its lines' spans and its metadata maps' entries."""
    name, lines, event_md, stat_md = "", [], [], []
    for f, v in _fields(b, *span):
        if f == _PLANE_NAME:
            name = _str(b, v)
        elif f == _PLANE_LINES:
            lines.append(v)
        elif f == _PLANE_EVENT_MD:
            event_md.append(v)
        elif f == _PLANE_STAT_MD:
            stat_md.append(v)
    return name, lines, event_md, stat_md


def _map_values(b, entries):
    for span in entries:
        for f, v in _fields(b, *span):
            if f == _MAP_VALUE:
                yield v


def _event_metadata(b, entries, stat_md=(), stat=None):
    """``{metadata id: (name, value of the string stat named stat)}``."""
    stat_id = None
    for v in _map_values(b, stat_md):
        d = dict(_fields(b, *v))
        if _MD_NAME in d and _str(b, d[_MD_NAME]) == stat:
            stat_id = d[_MD_ID]
    out = {}
    for v in _map_values(b, entries):
        mid, name, val = None, "", ""
        for f, x in _fields(b, *v):
            if f == _MD_ID:
                mid = x
            elif f == _MD_NAME:
                name = _str(b, x)
            elif f == _MD_STATS and stat_id is not None:
                d = dict(_fields(b, *x))
                if d.get(_STAT_MD_ID) == stat_id and _STAT_STR in d:
                    val = _str(b, d[_STAT_STR])
        out[mid] = (name, val)
    return out


def _line(b, span):
    """A line's name, its timestamp and its events' spans."""
    name, ts, events = "", 0, []
    for f, v in _fields(b, *span):
        if f == _LINE_NAME:
            name = _str(b, v)
        elif f == _LINE_TIMESTAMP_NS:
            ts = v
        elif f == _LINE_EVENTS:
            events.append(v)
    return name, ts, events


def _events(b, ts, events, want=None):
    """``(metadata id, start_ns, duration_ns)`` of each event; ``want``
    keeps only the events of those metadata ids."""
    out = []
    for ev in events:
        mid, off, dur = None, None, 0
        for f, x in _fields(b, *ev):
            if f == _EVENT_MD_ID:
                mid = x
                if want is not None and mid not in want:
                    break
            elif f == _EVENT_OFFSET_PS:
                off = x
            elif f == _EVENT_DURATION_PS:
                dur = x
        if off is not None and (want is None or mid in want):
            out.append((mid, ts + off // 1000, dur // 1000))
    return out


def load(path, window_name: str = "bench_window") -> dict:
    b = memoryview(Path(path).read_bytes())
    wins, ops = [], []
    for f, span in _fields(b, 0, len(b)):
        if f != _SPACE_PLANES:
            continue
        name, lines, event_md, stat_md = _plane(b, span)
        if name.startswith("/host:CPU"):
            want = {k for k, (n, _) in _event_metadata(b, event_md).items()
                    if n == window_name}
            for line in lines:
                _, ts, evs = _line(b, line)
                wins += [(s, s + d) for _, s, d in _events(b, ts, evs, want)]
        elif name.startswith("/device:"):
            md = _event_metadata(b, event_md, stat_md, "tf_op")
            for line in lines:
                lname, ts, evs = _line(b, line)
                if lname == "XLA Ops":
                    ops += [[name, n.split(" = ")[0], path, s, d]
                            for m, s, d in _events(b, ts, evs)
                            for n, path in [md.get(m, ("", ""))]]
    if not wins:
        raise ValueError(f"no host event {window_name!r} in the trace")
    lo, hi = max(wins, key=lambda w: w[1] - w[0])
    return {"window": [lo, hi],
            "ops": [o for o in ops if o[3] < hi and o[3] + o[4] > lo]}


def scope_of(path: str, scopes: Sequence[str] = SCOPES) -> Optional[str]:
    parts = path.split("/")
    return next((s for s in scopes if s in parts), None)


def _union_ns(iv) -> int:
    total, end = 0, None
    for a, b in sorted(iv):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _self_ns(ops) -> Dict[tuple, int]:
    """``ops``: ``(a, b, start, key)``, clipped to the window; ``start``
    is the unclipped start, which orders the nesting (the op that started
    last is the innermost; of two that started together, the shorter).
    Each instant of the union goes to the innermost op's ``key``."""
    out: Dict[tuple, int] = defaultdict(int)
    ops = sorted(ops, key=lambda o: o[0])
    bounds = sorted({t for a, b, *_ in ops for t in (a, b)})
    heap: list = []
    i = 0
    for t, nxt in zip(bounds, bounds[1:]):
        while i < len(ops) and ops[i][0] <= t:
            a, b, start, key = ops[i]
            heapq.heappush(heap, (-start, b, i, key))
            i += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        if heap:
            out[heap[0][3]] += nxt - t
    return out


def reduce(events: dict, scopes: Sequence[str] = SCOPES) -> dict:
    lo, hi = events["window"]
    devices = sorted({o[0] for o in events["ops"]}) or ["none"]
    union = dict.fromkeys(scopes, 0)
    self_ = dict.fromkeys(scopes, 0)
    outside: Dict[str, int] = defaultdict(int)
    busy = 0
    for dev in devices:
        ops = [(max(s, lo), min(s + d, hi), s, (scope_of(p, scopes), n))
               for dv, n, p, s, d in events["ops"] if dv == dev]
        ops = [o for o in ops if o[1] > o[0]]
        busy += _union_ns([o[:2] for o in ops])
        for sc in scopes:
            union[sc] += _union_ns([o[:2] for o in ops if o[3][0] == sc])
        for (sc, n), ns in _self_ns(ops).items():
            if sc is None:
                outside[n] += ns
            else:
                self_[sc] += ns
    per = 1e9 * len(devices)
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / per,
            "union_s": {k: v / per for k, v in union.items()},
            "self_s": {k: v / per for k, v in self_.items()},
            "outside_s": dict(sorted(((k, v / per) for k, v in outside.items()),
                                     key=lambda kv: -kv[1]))}


def window_breakdown(ctx: dict) -> Optional[dict]:
    """``reduce`` of the run's traced window, computed once and kept in
    ``ctx``; None when the run left no trace."""
    if "scopes" not in ctx:
        from bench import harness
        ctx["scopes"] = None
        cell = ctx.get("cell")
        tdir = None if cell is None else harness.RUNS / "trace" / cell.name
        path = (next(tdir.rglob("*.xplane.pb"), None)
                if tdir is not None and tdir.is_dir() else None)
        if path is not None:
            ctx["scopes"] = reduce(load(path))
    return ctx["scopes"]


def ms_per_step(ctx: dict, scope: str) -> Optional[float]:
    """Device ms a window step under ``scope`` (the union of its ops'
    intervals over ``ctx["steps"]``); None where no op carries the scope,
    as in a program that does not name it."""
    r = window_breakdown(ctx)
    steps = ctx.get("steps")
    if r is None or not steps or r["union_s"].get(scope, 0.0) <= 0:
        return None
    return 1e3 * r["union_s"][scope] / steps



if __name__ == "__main__":
    # python3 -m bench.scopes <the window's .xplane.pb>: the breakdown
    import json
    import sys
    print(json.dumps(reduce(load(sys.argv[1])), indent=1))
