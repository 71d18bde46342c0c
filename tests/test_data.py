"""Data pipeline: determinism, host sharding, restart safety."""
import numpy as np
import pytest

from repro.data.pipeline import DataConfig, TokenPipeline


def test_deterministic_across_instances():
    cfg = DataConfig(vocab_size=100, seq_len=32, global_batch=4, seed=3)
    a = TokenPipeline(cfg).batch(5)["tokens"]
    b = TokenPipeline(cfg).batch(5)["tokens"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_steps_differ():
    cfg = DataConfig(vocab_size=100, seq_len=32, global_batch=4)
    a = TokenPipeline(cfg).batch(1)["tokens"]
    b = TokenPipeline(cfg).batch(2)["tokens"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_host_shards_partition_global_batch():
    cfg = DataConfig(vocab_size=100, seq_len=16, global_batch=8)
    full = TokenPipeline(cfg).global_batch_all_hosts(3)["tokens"]
    parts = [TokenPipeline(cfg, host_id=h, num_hosts=4).batch(3)["tokens"]
             for h in range(4)]
    np.testing.assert_array_equal(
        np.asarray(full), np.concatenate([np.asarray(p) for p in parts]))


def test_tokens_in_range():
    cfg = DataConfig(vocab_size=77, seq_len=64, global_batch=2)
    t = np.asarray(TokenPipeline(cfg).batch(0)["tokens"])
    assert t.min() >= 0 and t.max() < 77


def test_learnable_structure():
    """The synthetic stream has deterministic successors (models can learn)."""
    cfg = DataConfig(vocab_size=100, seq_len=256, global_batch=1)
    t = np.asarray(TokenPipeline(cfg).batch(0)["tokens"])[0]
    pred = (t[:-1] * 31 + np.arange(cfg.structure)[:, None] * 7 + 13) % 100
    frac = max((pred[i] == t[1:]).mean() for i in range(cfg.structure))
    assert frac > 0.5  # one theme explains most transitions


def test_file_source(tmp_path):
    p = tmp_path / "corpus.txt"
    p.write_text("hello world, this is a tiny corpus for testing " * 50)
    cfg = DataConfig(vocab_size=256, seq_len=32, global_batch=2,
                     source="file", path=str(p))
    pipe = TokenPipeline(cfg)
    b = np.asarray(pipe.batch(0)["tokens"])
    assert b.shape == (2, 32) and b.max() < 256
    b2 = np.asarray(TokenPipeline(cfg).batch(0)["tokens"])
    np.testing.assert_array_equal(b, b2)


def _loop_row(seed, step, row, seq, vocab, structure):
    """The stream token by token: the same draws, each successor from the
    one before."""
    import hashlib
    h = hashlib.blake2b(f"{seed}:{step}:{row}".encode(), digest_size=8)
    rng = np.random.default_rng(int.from_bytes(h.digest(), "little"))
    theme = rng.integers(0, structure)
    toks = [int(rng.integers(0, vocab))]
    noise, rand = rng.random(seq), rng.integers(0, vocab, seq)
    for t in range(1, seq):
        toks.append(int(rand[t]) if noise[t] < 0.15
                    else (toks[-1] * 31 + int(theme) * 7 + 13) % vocab)
    return np.array(toks, np.int32)


@pytest.mark.parametrize("vocab,seq,structure,seed", [
    (12800, 2048, 64, 3170170111), (92544, 512, 64, 2 ** 31 + 5),
    (77, 300, 3, 0), (1, 16, 4, 7), (2 ** 20 + 7, 256, 9, 11)])
def test_synthetic_rows_equal_the_token_by_token_stream(vocab, seq,
                                                        structure, seed):
    cfg = DataConfig(vocab_size=vocab, seq_len=seq, global_batch=3,
                     seed=seed, structure=structure)
    got = np.asarray(TokenPipeline(cfg).batch(4)["tokens"])
    want = np.stack([_loop_row(seed, 4, r, seq, vocab, structure)
                     for r in range(3)])
    np.testing.assert_array_equal(got, want)
