"""Train step, masked update: device ms a window step of the ops under
the ``blockllm_update`` scope, their intervals' union (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "blockllm_update")
