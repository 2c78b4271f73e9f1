"""The synthetic token stream of bench/tokens.py."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import tokens as tok  # noqa: E402


def first_draw(vocab, seed):
    rng = np.random.default_rng(seed)
    return int(rng.integers(2, max(3, vocab - 1))), int(rng.integers(1, vocab))


def test_same_seed_same_batches_labels_shifted():
    a, b = tok.TokenStream(512, 64, 2, 2**33 + 7), tok.TokenStream(512, 64, 2, 2**33 + 7)
    for i in (0, 5):
        (xa, ya), (xb, yb) = a.batch(i), b.batch(i)
        assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
        assert np.array_equal(xa[:, 1:], ya[:, :-1])
        assert xa.dtype == np.int32 and xa.shape == (2, 64) and 0 <= xa.min() and xa.max() < 512


@pytest.mark.parametrize("seed,first_max", [(3000000005, 3268), (3000000000, 15)])
def test_a_map_that_repeats_one_token_is_drawn_again(seed, first_max):
    """Seed 3000000005's first map sends 3,268 of 4,096 tokens to one id;
    seed 3000000000's first map spreads them and is kept."""
    st = tok.TokenStream(512, 2048, 2, seed)
    redrawn = (st.a, st.b) != first_draw(512, seed)
    assert redrawn == (first_max > tok.MAX_REPEAT)
    assert np.bincount(st.batch(0)[0].ravel()).max() <= tok.MAX_REPEAT
