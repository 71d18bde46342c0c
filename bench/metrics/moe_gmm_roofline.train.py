"""Train step, experts: the grouped matmuls' share of their roofline, in
%: the least time the chip could take for their work, the larger of
FLOPs over the bf16 peak and bytes over the HBM bandwidth, over the
device time of the ops under the ``moe_gmm`` scope (their intervals'
union a window step, found in op paths as ``moe_ms.train`` finds
``moe``).

The work is counted from the configuration, as ``mfu.train`` counts its
FLOPs (``work``): ``T k held / E`` rows a MoE layer, the count
``bench/flops.py`` uses, through four passes (the forward, its recompute
under remat, and the backward's dX and dW), each ``2 x 3 d f`` FLOPs a
row and the held experts' bfloat16 weights plus the rows in and out
(``3 (d + f)`` bfloat16 elements a row).  None where no op carries the
scope or the configuration has no held experts."""
import importlib.util
from pathlib import Path

SCOPE = "moe_gmm"
PASSES = 4
BF16 = 2


def _moe_ms():
    spec = importlib.util.spec_from_file_location(
        "bench_metric_moe_ms_train", Path(__file__).with_name("moe_ms.train.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def work(m: dict, tokens: int):
    """(FLOPs, bytes) of one step's grouped matmuls."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    held, k = m["n_routed_experts"], m["num_experts_per_tok"]
    E = m.get("router_experts", held)
    layers = m["num_hidden_layers"] - m.get("first_k_dense_replace", 0)
    rows = tokens * k * held / E
    flops = PASSES * layers * 2 * 3 * d * f * rows
    nbytes = PASSES * layers * BF16 * (3 * d * f * held + 3 * (d + f) * rows)
    return flops, nbytes


def read(ctx):
    cell, peaks = ctx.get("cell"), ctx.get("peaks")
    if cell is None or peaks is None or "moe_intermediate_size" not in cell.config:
        return None
    mod = _moe_ms()
    ms = mod.union_ms_per_step(mod.events(ctx), ctx.get("steps"), SCOPE)
    if ms is None:
        return None
    flops, nbytes = work(cell.config,
                         cell.traffic["batch"] * cell.traffic["seq_len"])
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
