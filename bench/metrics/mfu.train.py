"""Train step: model FLOP/s utilisation, in % of the chip's bf16 peak.
FLOPs per token by ``bench/flops.py`` (6 N over the parameters a token
goes through plus the attention's, BlockLLM's pruned weight gradients
counted as if done) times the window's tokens/s."""


def read(ctx):
    if ctx.get("peaks") is None:
        return None
    if not ctx.get("steps"):
        return None
    return 100.0 * ctx["tokens_per_s"] * ctx["flops_per_token"] \
        / ctx["peaks"]["bf16_flops_per_s"]
