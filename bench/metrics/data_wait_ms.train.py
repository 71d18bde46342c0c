"""Train loop: mean time of the loop's ``data`` span per step, in ms
(the host making and handing over the step's batch)."""


def read(ctx):
    d = [t1 - t0 for name, t0, t1 in ctx.get("spans", []) if name == "data"]
    return sum(d) / len(d) / 1e6 if d else None
