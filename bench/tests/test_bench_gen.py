"""The reference's copy of the training rows: the same rows the program's
``TokenPipeline`` feeds, seeded and distinct from step to step."""
import numpy as np
import pytest

from bench import gen

BIG = 2 ** 31 + 987654321


@pytest.mark.parametrize("seed,step", [(BIG, 0), (BIG, 5), (7, 1)])
def test_rows_equal_the_programs_pipeline(seed, step):
    from repro.data.pipeline import DataConfig, TokenPipeline
    pipe = TokenPipeline(DataConfig(vocab_size=92544, seq_len=256,
                                    global_batch=2, seed=seed, structure=64))
    want = np.asarray(pipe.batch(step)["tokens"])
    got = gen.train_rows(seed, step, 2, 256, 92544, 64)
    assert got.dtype == np.int32 and got.shape == (2, 256)
    assert (got == want).all()


def test_rows_are_seeded_and_distinct_per_step():
    a = gen.train_rows(BIG, 0, 2, 256, 92544, 64)
    assert (a == gen.train_rows(BIG, 0, 2, 256, 92544, 64)).all()
    assert (a != gen.train_rows(BIG, 1, 2, 256, 92544, 64)).any()
    assert (a != gen.train_rows(BIG + 1, 0, 2, 256, 92544, 64)).any()
    assert (a[0] != a[1]).any()
    assert a.min() >= 0 and a.max() < 92544
    # about 15% of tokens are drawn at random, the rest follow the theme
    follow = (a[:, 1:] == (a[:, :-1].astype(np.int64) * 31 + 13
                           + 7 * np.arange(64)[:, None, None]) % 92544).any(0)
    assert 0.8 < follow.mean() < 0.9
