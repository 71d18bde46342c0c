"""Train step, attention: device ms a window step of the ops under the
``flash_attn`` scope (``kernels/ops.flash_attention``), their intervals'
union (bench/scopes.py).  The scope is found as a component of an op's
path, bare or inside a transform's name (``transpose(jvp(flash_attn))``),
so the forward, its recompute under remat and the backward kernels all
count.  None where no op carries it: a program whose attention does not
run as the flash kernel."""
import re

from bench import harness, scopes

SCOPE = "flash_attn"
_IN_PATH = re.compile(r"(^|[/(])" + SCOPE + r"([/)]|$)")


def _events(ctx):
    """The window's device ops, each op's path cut down to the scope or
    nothing; loaded once and kept in ``ctx``."""
    if "attn_events" not in ctx:
        ctx["attn_events"] = None
        cell = ctx.get("cell")
        tdir = None if cell is None else harness.RUNS / "trace" / cell.name
        path = (next(tdir.rglob("*.xplane.pb"), None)
                if tdir is not None and tdir.is_dir() else None)
        if path is not None:
            ctx["attn_events"] = scopes.load(path)
    return ctx["attn_events"]


def ms_per_step(events, steps):
    if events is None or not steps:
        return None
    ops = [[dev, name, SCOPE if _IN_PATH.search(path) else "", s, d]
           for dev, name, path, s, d in events["ops"]]
    r = scopes.reduce({"window": events["window"], "ops": ops},
                      scopes=(SCOPE,))
    union = r["union_s"][SCOPE]
    return 1e3 * union / steps if union > 0 else None


def read(ctx):
    return ms_per_step(_events(ctx), ctx.get("steps"))
