"""Warm-up runs one recorded step per compile key through the executor's
own ``execute``: the step lands on the key a served step of the same shape
reaches, and hands every page back."""
import math

import pytest

import drive
import lm

TOY = {"name": "toy", "source": "https://example.org/toy", "hidden_size": 64,
       "intermediate_size": 128, "num_attention_heads": 4,
       "num_key_value_heads": 2, "num_hidden_layers": 1, "vocab_size": 64,
       "rope_theta": 1e4, "rms_norm_eps": 1e-5, "sliding_window": None,
       "serving": {"num_pages": 96, "page_size": 16, "max_pages_per_seq": 32,
                   "kv_dtype": "fp32"}}


@pytest.fixture(scope="module")
def executor():
    s = lm.shape_of(TOY)
    return drive.build_executor(TOY, s, lm.make_weights(s, 0))


@pytest.mark.parametrize("shape", [(4, 4, 1, 300), (9, 9, 1, 40),
                                   (100, 7, 40, 500), (64, 3, 32, 32),
                                   (1, 1, 1, 1)])
def test_warm_step_lands_on_the_served_key(executor, shape):
    from repro.engine.executor import _bucket, _ladder

    ex = executor
    free = ex.alloc.free_blocks
    ex.compile_keys.clear()
    drive.warm_step(ex, shape)
    n_tok, n, m, ctx = shape
    pages = min(ex.max_pages, _ladder(math.ceil(ctx / ex.page_size), 2))
    assert ex.compile_keys == {("fused", _ladder(n_tok, 4), _ladder(n, 4),
                                _bucket(m, 1), pages)}
    assert ex.alloc.free_blocks == free


def test_step_shape_counts():
    assert drive.step_shape([(0, 5, 5), (40, 1, 41), (7, 3, 10)]) == \
        (9, 3, 5, 41)
