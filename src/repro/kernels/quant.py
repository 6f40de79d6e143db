"""Quantized KV-cache number formats (DESIGN.md §14).

The paged KV cache stores values in int8 or fp8-e4m3 with one f32
dequantization scale per (token, kv-head)
row: ``scale = absmax(row) / qmax`` and ``value ≈ stored * scale``. Scales
live in dedicated scale pages managed by ``BlockAllocator`` (one scale page
per data page — see engine/kv_manager.py), so COW/fork/evict semantics are
identical for values and scales.

Error bound: for int8 the dequantization error of any element in a row with
absmax ``a`` is at most half a quantization step, ``a / (2·127)``. For
fp8-e4m3 (3 mantissa bits) the round-to-nearest cast error is relative,
``|x| · 2^-4`` per element, bounded here by the conservative per-row
absolute form ``a · 2^-4``. ``row_error_bound`` exposes exactly the bound
the numerics tests and DESIGN.md §14 derive the attention output tolerance
from.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

_EPS = 1e-12      # floor for all-zero rows: scale 0 would make dequant 0/0


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """One KV storage format: dtype, quantization range, error step."""
    name: str
    dtype: jnp.dtype
    qmax: float           # largest representable magnitude after scaling
    half_step: float      # per-row error bound as a fraction of row absmax
    bytes_per_elt: int


_INT8 = QuantSpec("int8", jnp.int8, 127.0, 0.5 / 127.0, 1)
# e4m3fn max finite = 448; 3 mantissa bits → half-ulp relative 2^-4
_FP8 = QuantSpec("fp8_e4m3", jnp.float8_e4m3fn, 448.0, 2.0 ** -4, 1)


def kv_quant_spec(kv_dtype: str) -> Optional[QuantSpec]:
    """Resolve a kv_dtype string; None means unquantized fp32 storage."""
    if kv_dtype in ("fp32", "float32", None):
        return None
    if kv_dtype == "int8":
        return _INT8
    if kv_dtype == "fp8_e4m3":
        return _FP8
    raise ValueError(f"unknown kv_dtype {kv_dtype!r}")


def quantize_kv(x, spec: QuantSpec):
    """Per-(…, row) absmax quantization over the trailing (head_dim) axis.

    x: (..., D) f32 → (values (..., D) spec.dtype, scales (...,) f32) with
    ``x ≈ values * scales[..., None]``. The idiom follows the Pallas TPU
    quantization-kernel pattern (absmax/qmax scale, clip, round-to-nearest).
    """
    amax = jnp.max(jnp.abs(x), axis=-1)
    scales = jnp.maximum(amax, _EPS) / spec.qmax
    scaled = x / scales[..., None]
    if spec.dtype == jnp.int8:
        values = jnp.clip(jnp.round(scaled), -spec.qmax, spec.qmax)
    else:
        values = jnp.clip(scaled, -spec.qmax, spec.qmax)
    return values.astype(spec.dtype), scales.astype(jnp.float32)


def dequantize_kv(values, scales):
    """values: (..., D) quantized; scales: (...,) f32 → (..., D) f32."""
    return values.astype(jnp.float32) * scales[..., None].astype(jnp.float32)


def row_error_bound(x, spec: QuantSpec):
    """Per-row bound on |dequantize(quantize(x)) - x| (elementwise), (...,).

    This is the documented DESIGN.md §14 bound the numerics sweep asserts:
    half a quantization step of the row's absmax.
    """
    return jnp.max(jnp.abs(x), axis=-1) * spec.half_step + _EPS
