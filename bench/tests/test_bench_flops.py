"""Operation counts against hand counts at a tiny size, and the
peak table."""
import json
from pathlib import Path

import pytest

from bench import flops, peaks

TINY = json.loads((Path(__file__).parent / "data" / "tiny.json").read_text())
# L=2, d=64, H=4, KV=2, hd=16, f=128, V=256


def test_non_embedding_params_by_hand():
    layer = 64 + 64 * 64 + 2 * 64 * 32 + 64 * 64 + 64 + 3 * 64 * 128
    assert flops.non_embedding_params(TINY) == 2 * layer + 64 + 64 * 256


def test_train_step_by_hand():
    n = flops.non_embedding_params(TINY)
    # 6N per token, plus 12 L d S for the attention scores at S = 32
    assert flops.train_flops_per_token(TINY, 32) == 6 * n + 12 * 2 * 64 * 32


def test_peaks_known_kind_and_unknown_kind_raises():
    p = peaks.for_device("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.for_device("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.for_device("cpu")


def test_internlm2_train_step_to_the_flop():
    m = json.loads((Path(__file__).parents[1] / "configs"
                    / "internlm2-1.8b.json").read_text())
    assert flops.train_flops_per_token(m, 2048) == 11405438976


def test_moe_train_step_by_hand():
    m = json.loads((Path(__file__).parent / "data" / "tiny-moe.json").read_text())
    # L=2, d=64, H=4, KV=2, hd=16, E=8, k=2, f=32, one shared expert, V=256
    attn = 64 + 64 * 64 + 2 * 64 * 32 + 64 * 64 + 64
    router, expert, shared = 64 * 8, 3 * 64 * 32, 3 * 64 * 32
    layer_all = attn + router + 8 * expert + shared
    assert flops.non_embedding_params(m) == 2 * layer_all + 64 + 64 * 256
    active = 2 * (attn + router + 2 * expert + shared) + 64 + 64 * 256
    assert flops.train_flops_per_token(m, 32) == 6 * active + 12 * 2 * 64 * 32
