"""Train step, experts: device ms a window step of the ops under the
``moe`` scope (``models/moe.moe_apply``: router, sort, gather, grouped
matmuls, combine, shared experts, balance loss), their intervals' union
(bench/scopes.py).  The scope is found as a component of an op's path,
bare or inside a transform's name (``transpose(jvp(moe))``), so the
forward, its recompute under remat and the backward all count.  None
where no op carries it: a program without the held-experts layer."""
import re

from bench import harness, scopes

SCOPE = "moe"


def events(ctx):
    """The window's device ops with their scope paths; loaded once and
    kept in ``ctx``."""
    if "scope_events" not in ctx:
        ctx["scope_events"] = None
        cell = ctx.get("cell")
        tdir = None if cell is None else harness.RUNS / "trace" / cell.name
        path = (next(tdir.rglob("*.xplane.pb"), None)
                if tdir is not None and tdir.is_dir() else None)
        if path is not None:
            ctx["scope_events"] = scopes.load(path)
    return ctx["scope_events"]


def union_ms_per_step(events, steps, scope):
    """Device ms a step of the ops whose path holds ``scope``; None where
    none does."""
    if events is None or not steps:
        return None
    pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    ops = [[dev, name, scope if pat.search(path) else "", s, d]
           for dev, name, path, s, d in events["ops"]]
    r = scopes.reduce({"window": events["window"], "ops": ops},
                      scopes=(scope,))
    union = r["union_s"][scope]
    return 1e3 * union / steps if union > 0 else None


def read(ctx):
    return union_ms_per_step(events(ctx), ctx.get("steps"), SCOPE)
