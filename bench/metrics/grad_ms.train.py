"""Train step, forward and backward: device ms a window step of the ops
under the ``blockllm_grad`` scope, their intervals' union (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "blockllm_grad")
