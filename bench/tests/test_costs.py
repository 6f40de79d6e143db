"""Operation and byte counts against hand counts at published widths."""
import pytest

from costs import (attended_keys, attention_bytes, attention_flops,
                   roofline_seconds, step_flops)
from lm import Shape

DANUBE = Shape(n_layers=24, d_model=2560, n_heads=32, n_kv_heads=8,
               head_dim=80, d_ff=6912, vocab=32000, window=4096,
               rope_theta=1e4, norm_eps=1e-5)
STABLELM = Shape(n_layers=16, d_model=2560, n_heads=32, n_kv_heads=32,
                 head_dim=80, d_ff=6912, vocab=50304, window=None,
                 rope_theta=1e4, norm_eps=1e-5)


def test_attended_keys_causal_and_window():
    assert attended_keys(0, 4, None) == 1 + 2 + 3 + 4
    assert attended_keys(10, 1, None) == 11
    assert attended_keys(4095, 2, 4096) == 4096 + 4096


@pytest.mark.parametrize("s", [DANUBE, STABLELM], ids=["danube", "stablelm"])
def test_decode_token_counts(s):
    # one decode token at position 999 (context 1000) in every layer
    seqs = [(999, 1, 1000)]
    flops = attention_flops(s, seqs)
    assert flops == 4 * s.n_heads * s.head_dim * 1000 * s.n_layers
    kv = 2 * 1000 * s.n_kv_heads * s.head_dim * 4
    qo = 2 * 1 * s.n_heads * s.head_dim * 4
    assert attention_bytes(s, seqs) == (kv + qo) * s.n_layers


def test_danube_hand_counts():
    # 100-token prefill chunk at positions 0..99: keys 1+...+100 = 5050
    seqs = [(0, 100, 100)]
    assert attention_flops(DANUBE, seqs) == 4 * 32 * 80 * 5050 * 24
    # K and V rows 0..99 once (8 kv heads x 80), q and o of 100 tokens
    assert attention_bytes(DANUBE, seqs) == (2 * 100 * 640 * 4
                                             + 2 * 100 * 2560 * 4) * 24
    layer = 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912
    assert DANUBE.layer_matmul_params() == layer
    assert step_flops(DANUBE, seqs) == (2 * layer * 24 * 100
                                        + 2 * 2560 * 32000
                                        + attention_flops(DANUBE, seqs))


def test_stablelm_hand_counts():
    layer = 4 * 2560 * 2560 + 3 * 2560 * 6912
    assert STABLELM.layer_matmul_params() == layer
    seqs = [(0, 1, 1), (499, 1, 500)]
    assert step_flops(STABLELM, seqs) == (2 * layer * 16 * 2
                                          + 2 * 2 * 2560 * 50304
                                          + 4 * 32 * 80 * 501 * 16)


def test_window_limits_the_bytes_read():
    # a token at position 5000 under a 4096 window reads 4096 K/V rows
    got = attention_bytes(DANUBE, [(5000, 1, 5001)])
    assert got == (2 * 4096 * 640 * 4 + 2 * 2560 * 4) * 24


def test_roofline_picks_the_binding_peak():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline_seconds(1000.0, 50.0, peaks) == (10.0, "compute")
    assert roofline_seconds(100.0, 50.0, peaks) == (5.0, "memory")
