"""The model side of the yardstick: the configuration as it is run, the
weights drawn from the seed, and the plain reference forward pass.

Nothing here imports the program. The weights are made here, on the device,
in one jitted call, in the layout the program serves (stacked per-layer
leaves) and in float32, the type they are served in. The reference is a
straightforward decoder in ``jax.numpy``: RMSNorm with a ``1 + w`` gain,
rotary embeddings on the whole head (the two halves rotated against each
other), grouped-query causal attention with an optional sliding window, and
a SiLU-gated MLP. It runs in float32 at ``highest`` matmul precision, or in
bfloat16 for the control.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the benchmark needs, read from a configuration file."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    window: int | None
    rope_theta: float
    norm_eps: float

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def layer_matmul_params(self) -> int:
        d = self.d_model
        return 2 * d * self.q_dim + 2 * d * self.kv_dim + 3 * d * self.d_ff


def shape_of(conf: dict) -> Shape:
    """HF-style keys of a configuration file → the sizes the run uses."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return Shape(n_layers=conf["num_hidden_layers"], d_model=d, n_heads=h,
                 n_kv_heads=conf["num_key_value_heads"],
                 head_dim=conf.get("head_dim", d // h),
                 d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                 window=conf.get("sliding_window"),
                 rope_theta=float(conf["rope_theta"]),
                 norm_eps=float(conf["rms_norm_eps"]))


def weight_key(seed: int):
    """A 32-bit PRNG key from any whole-number seed (seeds may exceed 2**31)."""
    return jax.random.PRNGKey(
        int(np.random.default_rng(int(seed)).integers(0, 2**31 - 1)))


def init_weights(s: Shape, key, dtype=jnp.float32) -> dict:
    """Random weights with unit-scale activations: matrices N(0, 1/fan_in),
    norm gains N(0, 0.1^2) (the norm applies 1 + gain), embeddings N(0, 1)."""
    L, d = s.n_layers, s.d_model
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return jax.random.normal(next(ks), shape, dtype) / math.sqrt(fan_in)

    def gain(shape):
        return 0.1 * jax.random.normal(next(ks), shape, dtype)

    return {
        "embed": jax.random.normal(next(ks), (s.vocab, d), dtype),
        "ln_f": gain((d,)),
        "head": mat((d, s.vocab), d),
        "layers": {
            "attn": {"wq": mat((L, d, s.q_dim), d),
                     "wk": mat((L, d, s.kv_dim), d),
                     "wv": mat((L, d, s.kv_dim), d),
                     "wo": mat((L, s.q_dim, d), s.q_dim)},
            "ln1": gain((L, d)),
            "ln2": gain((L, d)),
            "mlp": {"w_gate": mat((L, d, s.d_ff), d),
                    "w_up": mat((L, d, s.d_ff), d),
                    "w_down": mat((L, s.d_ff, d), s.d_ff)},
        },
    }


def make_weights(s: Shape, seed: int) -> dict:
    """All weights on the default device, in one jitted program."""
    w = jax.jit(lambda k: init_weights(s, k))(weight_key(seed))
    return jax.block_until_ready(w)


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------

Q_BLOCK = 512        # query rows per attention block (bounds score memory)
LEN_BUCKET = 512     # sequences are padded to a power-of-two multiple of this


def _rmsnorm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rope(x, pos, theta):
    """x: (T, H, D); rotate the first half of D against the second."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v, s: Shape):
    """Causal (windowed) GQA over one sequence: q (T, H, D), k/v (T, Hkv, D).
    Query head h reads kv head h // (H / Hkv)."""
    t = q.shape[0]
    g = s.n_heads // s.n_kv_heads
    scale = s.head_dim ** -0.5
    kpos = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        qb = qb.reshape(Q_BLOCK, s.n_kv_heads, g, s.head_dim)
        sc = jnp.einsum("thgd,shd->hgts", qb, k,
                        preferred_element_type=jnp.float32) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        mask = kpos[None, :] <= qpos[:, None]
        if s.window is not None:
            mask &= (qpos[:, None] - kpos[None, :]) < s.window
        sc = jnp.where(mask, sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        o = jnp.einsum("hgts,shd->thgd", p, v,
                       preferred_element_type=jnp.float32)
        return o.reshape(Q_BLOCK, s.q_dim).astype(q.dtype)

    return jax.lax.map(block, jnp.arange(t // Q_BLOCK)).reshape(t, s.q_dim)


def _forward_logits(w, tokens, s: Shape, dtype):
    """Logits (T, vocab) of one sequence, every position, in ``dtype``."""
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = w["embed"][tokens].astype(dtype)

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        h = _rmsnorm(x, lp["ln1"], s.norm_eps)
        a = lp["attn"]
        q = _rope((h @ a["wq"]).reshape(t, s.n_heads, s.head_dim), pos,
                  s.rope_theta)
        k = _rope((h @ a["wk"]).reshape(t, s.n_kv_heads, s.head_dim), pos,
                  s.rope_theta)
        v = (h @ a["wv"]).reshape(t, s.n_kv_heads, s.head_dim)
        x = x + _attention(q, k, v, s) @ a["wo"]
        h = _rmsnorm(x, lp["ln2"], s.norm_eps)
        m = lp["mlp"]
        x = x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    h = _rmsnorm(x, w["ln_f"].astype(dtype), s.norm_eps)
    return (h @ w["head"].astype(dtype)).astype(jnp.float32)


def padded_len(n: int) -> int:
    """The reference's length bucket: few buckets, so few programs."""
    b = LEN_BUCKET
    while b < n:
        b *= 2
    return b


class Reference:
    """The plain model over padded sequences, one jitted program per length
    bucket. ``gaps`` is the float32 reference at ``highest`` precision;
    ``argmax`` is the control's pick in ``dtype``."""

    def __init__(self, s: Shape, weights):
        self.s, self.w = s, weights

        def gaps(w, tokens, cand):
            with jax.default_matmul_precision("highest"):
                lg = _forward_logits(w, tokens, s, jnp.float32)
            pick = jnp.take_along_axis(lg, cand[:, None], 1)[:, 0]
            return jnp.max(lg, -1) - pick

        def argmax(w, tokens, dtype):
            return jnp.argmax(_forward_logits(w, tokens, s, dtype), -1)

        self._gaps = jax.jit(gaps)
        self._argmax = jax.jit(argmax, static_argnames="dtype")

    @staticmethod
    def padded(seq: list) -> np.ndarray:
        out = np.zeros(padded_len(len(seq)), np.int32)
        out[:len(seq)] = seq
        return out

    def served_gaps(self, prompt: list, served: list) -> np.ndarray:
        """For each served token, how far its reference logit lies below the
        reference's best at that position (0 where it is the argmax)."""
        seq = list(prompt) + list(served[:-1])
        toks = self.padded(seq)
        cand = np.zeros_like(toks)
        first = len(prompt) - 1
        cand[first:first + len(served)] = served
        with jax.default_matmul_precision("highest"):
            g = self._gaps(self.w, jnp.asarray(toks), jnp.asarray(cand))
        return np.asarray(g)[first:first + len(served)]

    def control_gaps(self, prompt: list, served: list,
                     dtype=jnp.bfloat16) -> np.ndarray:
        """The same positions, read for the token the lower precision puts
        first: the gap a ``dtype`` model would show in the program's place."""
        seq = list(prompt) + list(served[:-1])
        toks = jnp.asarray(self.padded(seq))
        first = len(prompt) - 1
        cand = self._argmax(self.w, toks, dtype)
        with jax.default_matmul_precision("highest"):
            g = self._gaps(self.w, toks, cand)
        return np.asarray(g)[first:first + len(served)]
