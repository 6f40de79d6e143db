"""The control of the served-token check, at a size a test run can hold.

On the chip the control runs at the cell's own size (``bench/control.py``):
the float32 reference put in the program's place in bfloat16, read at the
same positions as the served tokens. Here the same reading is made on the
CPU for a small model whose tokens were served greedily through the float32
reference itself, where the program's reading is exactly 0: the control
must read above it at some positions, and the reference must reproduce its
own greedy stream exactly.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import lm

SMALL = lm.Shape(n_layers=8, d_model=512, n_heads=8, n_kv_heads=2,
                 head_dim=64, d_ff=1024, vocab=8192, window=None,
                 rope_theta=1e4, norm_eps=1e-5)


def greedy(ref, prompt, n):
    """n tokens served greedily by the float32 reference, one at a time."""
    out = []
    for _ in range(n):
        toks = jnp.asarray(ref.padded(prompt + out))
        with jax_highest():
            lg = lm._forward_logits(ref.w, toks, ref.s, jnp.float32)
        out.append(int(jnp.argmax(lg[len(prompt) + len(out) - 1])))
    return out


def jax_highest():
    import jax

    return jax.default_matmul_precision("highest")


@pytest.mark.parametrize("seed", [0, 1])
def test_bf16_control_reads_above_the_exact_stream(seed):
    w = lm.make_weights(SMALL, seed)
    ref = lm.Reference(SMALL, w)
    prompt = np.random.default_rng(seed).integers(0, SMALL.vocab, 300).tolist()
    served = greedy(ref, prompt, 24)
    exact = ref.served_gaps(prompt, served)
    assert exact.max() == pytest.approx(0.0, abs=1e-5)
    # the control over a longer stretch of positions of the same prompt
    longer = served + np.random.default_rng(seed + 7).integers(
        0, SMALL.vocab, 200).tolist()
    ctl = ref.control_gaps(prompt, longer)
    assert ctl.max() > 100 * max(float(exact.max()), 1e-6)
    assert np.mean(ctl > 0) > 0
