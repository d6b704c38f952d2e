"""Quantization kernels (Pallas int8 + fp8 casts).

TPU-native equivalent of the reference's quantization CUDA kernels
(``csrc/quantization/``: quantize/dequantize int4/int8 symmetric/asymmetric
with group-wise scales, used by ZeRO++ qwZ weight all-gather and qgZ
quantized gradient reduce, and ``csrc/fp_quantizer/`` FP8).  Group-wise
layout: values are viewed as ``(num_groups, group_size)``; each group gets
its own scale (and offset when asymmetric) so a single outlier only damages
its group — the same layout the reference's swizzled-quant kernels use.

APIs:
- :func:`quantize` / :func:`dequantize` — int8 blockwise, symmetric or
  asymmetric, Pallas on TPU with identical-math jnp fallback.
- :func:`quantize_fp8` / :func:`dequantize_fp8` — scaled fp8 (e4m3) cast.
- the ZeRO++ qwZ/qgZ collectives live in ``comm/quantized.py`` and call
  these kernels for the wire payloads.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import routes

_LANE = 128


class QuantizedTensor(NamedTuple):
    """int8 payload + per-group scale/offset + original shape/dtype."""
    values: jax.Array        # int8 [num_groups, group_size]
    scale: jax.Array         # f32 [num_groups, 1]
    offset: jax.Array        # f32 [num_groups, 1] (zeros when symmetric)
    shape: Tuple[int, ...]
    dtype: jnp.dtype


def _quant_kernel(x_ref, v_ref, s_ref, o_ref, *, symmetric: bool,
                  q_max: float):
    x = x_ref[:].astype(jnp.float32)  # [rows=groups_block, group_size]
    if symmetric:
        absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        scale = jnp.maximum(absmax, 1e-12) / q_max
        offset = jnp.zeros_like(scale)
    else:
        mx = jnp.max(x, axis=1, keepdims=True)
        mn = jnp.min(x, axis=1, keepdims=True)
        scale = jnp.maximum(mx - mn, 1e-12) / (2.0 * q_max)
        offset = (mx + mn) * 0.5
    q = jnp.clip(jnp.round((x - offset) / scale), -q_max, q_max)
    v_ref[:] = q.astype(jnp.int8)
    s_ref[:] = scale
    o_ref[:] = offset


def _dequant_kernel(v_ref, s_ref, o_ref, x_ref):
    x_ref[:] = (v_ref[:].astype(jnp.float32) * s_ref[:] + o_ref[:]
                ).astype(x_ref.dtype)


def _grouped(x: jax.Array, group_size: int) -> Tuple[jax.Array, int]:
    n = x.size
    num_groups = pl.cdiv(n, group_size)
    flat = jnp.pad(x.reshape(-1).astype(jnp.float32),
                   (0, num_groups * group_size - n))
    return flat.reshape(num_groups, group_size), num_groups


def quantize(x: jax.Array, num_bits: int = 8, group_size: int = 2048,
             symmetric: bool = True, interpret: bool = False
             ) -> QuantizedTensor:
    """Blockwise int8/int4-range quantization (int4 values are stored in an
    int8 payload with the int4 range, matching the reference's unpacked
    debug layout; dense 2x4-bit packing is a wire-format concern of the
    qgZ collective)."""
    assert num_bits in (4, 8)
    q_max = float(2 ** (num_bits - 1) - 1)
    xg, num_groups = _grouped(x, group_size)

    if routes.take("quantize", interpret=interpret) != routes.XLA:
        rows_blk = min(256, num_groups)
        grid = (pl.cdiv(num_groups, rows_blk),)
        pad_rows = grid[0] * rows_blk - num_groups
        if pad_rows:
            xg = jnp.pad(xg, ((0, pad_rows), (0, 0)))
        blk = pl.BlockSpec((rows_blk, group_size), lambda i: (i, 0))
        sblk = pl.BlockSpec((rows_blk, 1), lambda i: (i, 0))
        v, s, o = pl.pallas_call(
            functools.partial(_quant_kernel, symmetric=symmetric,
                              q_max=q_max),
            grid=grid,
            in_specs=[blk],
            out_specs=[blk, sblk, sblk],
            out_shape=[
                jax.ShapeDtypeStruct(xg.shape, jnp.int8),
                jax.ShapeDtypeStruct((xg.shape[0], 1), jnp.float32),
                jax.ShapeDtypeStruct((xg.shape[0], 1), jnp.float32),
            ],
            interpret=interpret,
        )(xg)
        v, s, o = v[:num_groups], s[:num_groups], o[:num_groups]
    else:
        if symmetric:
            absmax = jnp.max(jnp.abs(xg), axis=1, keepdims=True)
            s = jnp.maximum(absmax, 1e-12) / q_max
            o = jnp.zeros_like(s)
        else:
            mx = jnp.max(xg, axis=1, keepdims=True)
            mn = jnp.min(xg, axis=1, keepdims=True)
            s = jnp.maximum(mx - mn, 1e-12) / (2.0 * q_max)
            o = (mx + mn) * 0.5
        v = jnp.clip(jnp.round((xg - o) / s), -q_max, q_max).astype(jnp.int8)
    return QuantizedTensor(values=v, scale=s, offset=o, shape=tuple(x.shape),
                           dtype=x.dtype)


def dequantize(qt: QuantizedTensor, interpret: bool = False) -> jax.Array:
    if routes.take("dequantize", interpret=interpret) != routes.XLA:
        num_groups, group_size = qt.values.shape
        rows_blk = min(256, num_groups)
        grid = (pl.cdiv(num_groups, rows_blk),)
        pad_rows = grid[0] * rows_blk - num_groups
        v, s, o = qt.values, qt.scale, qt.offset
        if pad_rows:
            v = jnp.pad(v, ((0, pad_rows), (0, 0)))
            s = jnp.pad(s, ((0, pad_rows), (0, 0)))
            o = jnp.pad(o, ((0, pad_rows), (0, 0)))
        blk = pl.BlockSpec((rows_blk, group_size), lambda i: (i, 0))
        sblk = pl.BlockSpec((rows_blk, 1), lambda i: (i, 0))
        x = pl.pallas_call(
            _dequant_kernel,
            grid=grid,
            in_specs=[blk, sblk, sblk],
            out_specs=blk,
            out_shape=jax.ShapeDtypeStruct(v.shape, jnp.float32),
            interpret=interpret,
        )(v, s, o)[:num_groups]
    else:
        x = qt.values.astype(jnp.float32) * qt.scale + qt.offset
    n = int(np.prod(qt.shape)) if qt.shape else 1
    return x.reshape(-1)[:n].reshape(qt.shape).astype(qt.dtype)


# ---------------------------------------------------------------------------
# FP8 (``csrc/fp_quantizer`` equivalent — straightforward on TPU: native
# fp8 dtypes + per-tensor scale)
# ---------------------------------------------------------------------------

class FP8Tensor(NamedTuple):
    values: jax.Array   # float8_e4m3fn
    scale: jax.Array    # f32 scalar
    shape: Tuple[int, ...]
    dtype: jnp.dtype


def quantize_fp8(x: jax.Array) -> FP8Tensor:
    absmax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-12)
    fp8_max = float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = absmax / fp8_max
    v = (x.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
    return FP8Tensor(values=v, scale=scale, shape=tuple(x.shape),
                     dtype=x.dtype)


def dequantize_fp8(ft: FP8Tensor) -> jax.Array:
    return (ft.values.astype(jnp.float32) * ft.scale).astype(ft.dtype)


# ---------------------------------------------------------------------------
# FP6 e3m2 (``csrc/fp6`` / FP6-LLM equivalent).  No native fp6 dtype
# exists, so encode/decode is explicit bit math: 1 sign + 3 exponent
# (bias 3) + 2 mantissa bits, subnormals at exponent 0, max normal 28.
# Four 6-bit codes pack into three bytes — 6 bits/param in HBM.
# ---------------------------------------------------------------------------

FP6_MAX = 28.0                       # (1 + 3/4) * 2^(7-3)
_FP6_BIAS = 3


class FP6Tensor(NamedTuple):
    values: jax.Array   # uint8 [num_groups, group_size * 3 // 4] packed
    scale: jax.Array    # f32 [num_groups, 1]
    shape: Tuple[int, ...]
    dtype: jnp.dtype
    group_size: int


def _fp6_encode(a: jax.Array) -> jax.Array:
    """|x| in [0, FP6_MAX] -> 5-bit magnitude code (3 exp | 2 mantissa),
    round-to-nearest."""
    a = jnp.clip(a, 0.0, FP6_MAX)
    e = jnp.floor(jnp.log2(jnp.maximum(a, 1e-30)))
    e = jnp.clip(e, 1 - _FP6_BIAS, 4)              # normal exponents
    sub = a < 2.0 ** (1 - _FP6_BIAS)               # subnormal range
    # subnormal: a = m/4 * 2^(1-bias) -> m = a * 16
    m_sub = jnp.round(a * (4.0 / 2.0 ** (1 - _FP6_BIAS)))
    # normal: a = (1 + m/4) * 2^e -> m = (a/2^e - 1) * 4
    m_norm = jnp.round((a / 2.0 ** e - 1.0) * 4.0)
    # mantissa rounding overflow (m == 4) bumps the exponent
    bump = m_norm >= 4.0
    e = jnp.where(bump, e + 1.0, e)
    m_norm = jnp.where(bump, 0.0, m_norm)
    over = e > 4.0
    e = jnp.where(over, 4.0, e)
    m_norm = jnp.where(over, 3.0, m_norm)
    exp_bits = jnp.where(sub, 0.0, e + _FP6_BIAS)
    m = jnp.where(sub, jnp.minimum(m_sub, 3.0), m_norm)
    return (exp_bits.astype(jnp.uint8) << 2) | m.astype(jnp.uint8)


def _fp6_decode(code: jax.Array) -> jax.Array:
    """5-bit magnitude code -> float32 value."""
    exp_bits = (code >> 2) & jnp.uint8(0x7)
    m = (code & jnp.uint8(0x3)).astype(jnp.float32)
    sub = exp_bits == 0
    val_sub = m / 4.0 * 2.0 ** (1 - _FP6_BIAS)
    val_norm = (1.0 + m / 4.0) * 2.0 ** (
        exp_bits.astype(jnp.float32) - _FP6_BIAS)
    return jnp.where(sub, val_sub, val_norm)


def _pack6(codes: jax.Array) -> jax.Array:
    """[G, gs] 6-bit codes -> [G, gs*3/4] packed bytes (4 codes/3 bytes)."""
    g, gs = codes.shape
    q = codes.reshape(g, gs // 4, 4).astype(jnp.uint32)
    word = (q[..., 0] | (q[..., 1] << 6) | (q[..., 2] << 12)
            | (q[..., 3] << 18))                   # 24 bits
    b0 = (word & 0xFF).astype(jnp.uint8)
    b1 = ((word >> 8) & 0xFF).astype(jnp.uint8)
    b2 = ((word >> 16) & 0xFF).astype(jnp.uint8)
    return jnp.stack([b0, b1, b2], axis=-1).reshape(g, gs * 3 // 4)


def _unpack6(packed: jax.Array, group_size: int) -> jax.Array:
    g = packed.shape[0]
    b = packed.reshape(g, group_size // 4, 3).astype(jnp.uint32)
    word = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
    codes = jnp.stack([word & 0x3F, (word >> 6) & 0x3F,
                       (word >> 12) & 0x3F, (word >> 18) & 0x3F], axis=-1)
    return codes.reshape(g, group_size).astype(jnp.uint8)


def quantize_fp6(x: jax.Array, group_size: int = 512) -> FP6Tensor:
    """Blockwise-scaled fp6 e3m2 quantization (reference FP6-LLM weight
    format, ``csrc/fp6``): each group scales its absmax onto FP6_MAX,
    then values round to the fp6 grid and pack 6 bits each."""
    shape, dtype = tuple(x.shape), x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    numel = flat.size
    gs = group_size
    while gs > 4 and (numel % gs or gs % 4):
        gs -= 1
    if numel % gs or gs % 4:
        pad = (-numel) % 4
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        gs = 4
    groups = flat.reshape(-1, gs)
    absmax = jnp.maximum(jnp.max(jnp.abs(groups), axis=1, keepdims=True),
                         1e-12)
    scale = absmax / FP6_MAX
    scaled = groups / scale
    sign = (scaled < 0).astype(jnp.uint8) << 5
    codes = sign | _fp6_encode(jnp.abs(scaled))
    return FP6Tensor(values=_pack6(codes), scale=scale, shape=shape,
                     dtype=dtype, group_size=gs)


def dequantize_fp6(ft: FP6Tensor) -> jax.Array:
    codes = _unpack6(ft.values, ft.group_size)
    mag = _fp6_decode(codes)
    sign = jnp.where((codes >> 5) & jnp.uint8(1), -1.0, 1.0)
    x = sign * mag * ft.scale
    n = int(np.prod(ft.shape)) if ft.shape else 1
    return x.reshape(-1)[:n].reshape(ft.shape).astype(ft.dtype)
