"""Train step, host orchestration: mean over the window's ``train_step``
spans of their duration less the ``device_wait`` span inside each, in ms
(the host's own time in the step: plan, dispatch, norm ingestion,
reselection, step metrics)."""


def read(ctx):
    spans = ctx.get("spans", [])
    waits = [(t0, t1) for name, t0, t1 in spans if name == "device_wait"]
    if not waits:
        return None
    own = [(t1 - t0) - sum(w1 - w0 for w0, w1 in waits
                           if t0 <= w0 and w1 <= t1)
           for name, t0, t1 in spans if name == "train_step"]
    return sum(own) / len(own) / 1e6 if own else None
