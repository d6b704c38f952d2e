"""Serving-side quantization: weight-only formats for the inference
engines.

TPU-native counterpart of the reference's inference quantization stack:
``csrc/fp_quantizer/quantize.cu`` (fp8/fp6 ``selective_dequant``),
``inference/v2/kernels/core_ops/cuda_linear/`` (FP6-LLM GEMM), and the
int8 ``replace_with_quantized_linear`` path.  Weights live in HBM in the
quantized format (int8 group-wise, fp8 e4m3, or packed fp6 e3m2 —
``ops/quantization.py``) and dequantize IN-JIT at use, where XLA fuses
the elementwise expansion into the consuming matmul's operand read — the
TPU equivalent of the reference's dequant-in-GEMM-prologue kernels.

KV-cache quantization (fp8/int8 paged pools with per-row-per-head
scales) lives in ``inference/paged/kv_pages.py`` — it is a storage-layout concern
of the blocked KV pool.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.quantization import (FP6Tensor, FP8Tensor,
                                            QuantizedTensor, dequantize,
                                            dequantize_fp6, dequantize_fp8,
                                            quantize, quantize_fp6,
                                            quantize_fp8)

WEIGHT_FORMATS = ("int8", "fp8", "fp6", "w8a8")

# matmul-bearing leaf names — norms/biases/scales stay high precision
# (the reference's policies quantize Linear/Embedding weights only)
_QUANT_LEAVES = frozenset(
    {"kernel", "embedding", "w1", "w2", "w3", "wi", "wo"})


@jax.tree_util.register_pytree_node_class
class QuantizedWeight:
    """Pytree wrapper for a quantized parameter: the payload/scale arrays
    are children (device_put/jit/donation all work), the layout metadata
    (format, original shape/dtype, group size) is STATIC aux data — the
    ops-level NamedTuples carry shape/dtype as pytree children, which
    breaks abstraction the moment they sit inside a params tree."""

    def __init__(self, fmt: str, arrays: Tuple[jax.Array, ...],
                 shape: Tuple[int, ...], dtype, group_size: int = 0):
        self.fmt = fmt
        self.arrays = tuple(arrays)
        self.shape = tuple(shape)
        self.dtype = jnp.dtype(dtype)
        self.group_size = int(group_size)

    def tree_flatten(self):
        return self.arrays, (self.fmt, self.shape, str(self.dtype),
                             self.group_size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        fmt, shape, dtype, group_size = aux
        return cls(fmt, tuple(children), shape, dtype, group_size)

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in self.arrays)


def _is_q(leaf) -> bool:
    return isinstance(leaf, QuantizedWeight)


def quantize_param_tree(params: Any, fmt: str, min_size: int = 1024,
                        group_size: int = 2048) -> Tuple[Any, int, int]:
    """Quantize every matmul-bearing leaf of ``params`` to ``fmt``.

    Returns ``(qtree, bytes_before, bytes_after)``; small leaves (<
    ``min_size`` elements) and non-matmul leaves pass through unchanged.
    ``group_size`` is the int8/fp6 blockwise-scale granularity
    (reference ``QuantizationConfig.group_size``); fp8 scales per
    tensor.

    ``fmt="w8a8"``: 2-D ``kernel`` leaves get PER-OUTPUT-CHANNEL
    symmetric int8 (scale constant along the contraction axis, so it
    factors out of an int8 x int8 MXU dot — the models consume these
    leaves natively through :func:`w8a8_dot_general`, the reference's
    W8A8 quantized-inference GEMM, ``csrc/quantization``); non-kernel
    matmul leaves (embeddings, stacked MoE experts) fall back to
    group-wise int8 with in-jit dequant.
    """
    assert fmt in WEIGHT_FORMATS, \
        f"quantize_weights={fmt!r}: expected one of {WEIGHT_FORMATS}"
    before = after = 0

    def q(path, leaf):
        nonlocal before, after
        before += leaf.size * leaf.dtype.itemsize
        name = str(getattr(path[-1], "key", path[-1]))
        if (leaf.ndim < 2 or leaf.size < min_size or
                name not in _QUANT_LEAVES):
            after += leaf.size * leaf.dtype.itemsize
            return leaf
        if fmt == "w8a8" and name == "kernel" and leaf.ndim == 2:
            s = jnp.max(jnp.abs(leaf.astype(jnp.float32)), axis=0)
            s = jnp.maximum(s, 1e-12) / 127.0
            v = jnp.clip(jnp.round(leaf.astype(jnp.float32) / s),
                         -127, 127).astype(jnp.int8)
            out = QuantizedWeight("w8a8", (v, s), leaf.shape, leaf.dtype)
        elif fmt in ("int8", "w8a8"):
            t = quantize(leaf, num_bits=8, group_size=group_size)
            out = QuantizedWeight("int8", (t.values, t.scale, t.offset),
                                  t.shape, t.dtype)
        elif fmt == "fp8":
            t = quantize_fp8(leaf)
            out = QuantizedWeight("fp8", (t.values, t.scale), t.shape,
                                  t.dtype)
        else:
            t = quantize_fp6(leaf, group_size=group_size)
            out = QuantizedWeight("fp6", (t.values, t.scale), t.shape,
                                  t.dtype, t.group_size)
        after += out.nbytes
        return out

    return (jax.tree_util.tree_map_with_path(q, params), before, after)


def dequantize_param_tree(qtree: Any, native_w8a8: bool = False) -> Any:
    """In-jit inverse of :func:`quantize_param_tree` (XLA fuses the
    expansion into consumers; quantized leaves never persist in HBM at
    full precision).  ``native_w8a8=True`` leaves "w8a8" leaves in place
    for a model whose Dense layers consume them through
    :func:`w8a8_dot_general` — the int8 payload then never expands to
    full precision at all."""

    def dq(leaf):
        if not isinstance(leaf, QuantizedWeight):
            return leaf
        if leaf.fmt == "w8a8":
            if native_w8a8:
                return leaf
            v, s = leaf.arrays
            return (v.astype(jnp.float32) * s).astype(leaf.dtype)
        if leaf.fmt == "int8":
            v, s, o = leaf.arrays
            return dequantize(QuantizedTensor(v, s, o, leaf.shape,
                                              leaf.dtype))
        if leaf.fmt == "fp8":
            v, s = leaf.arrays
            return dequantize_fp8(FP8Tensor(v, s, leaf.shape, leaf.dtype))
        v, s = leaf.arrays
        return dequantize_fp6(FP6Tensor(v, s, leaf.shape, leaf.dtype,
                                        leaf.group_size))

    return jax.tree_util.tree_map(dq, qtree, is_leaf=_is_q)


# ---------------------------------------------------------------------------
# Native W8A8 consumption (the reference's dequant-in-GEMM-prologue /
# W8A8 inference GEMMs, ``csrc/quantization`` + ``cuda_linear``): the
# model's Dense layers read the int8 payload DIRECTLY — activations
# dynamically quantize per row, the dot runs on the MXU's int8 path
# (int32 accumulation), and the two scales rescale the output.  Decode
# is weight-bandwidth-bound, so halving the weight bytes halves the
# decode floor — unlike tree-level dequant, which pays an extra
# full-precision materialization per dispatch.
# ---------------------------------------------------------------------------

def quant_promote_dtype(*args, dtype=None, **kw):
    """``nn.Dense.promote_dtype`` replacement: QuantizedWeight leaves
    pass through untouched (flax's default would jnp.asarray them)."""
    from flax.linen.dtypes import promote_dtype

    qs = [a if isinstance(a, QuantizedWeight) else None for a in args]
    proms = promote_dtype(*(None if q is not None else a
                            for q, a in zip(qs, args)), dtype=dtype, **kw)
    return [q if q is not None else p for q, p in zip(qs, proms)]


def w8a8_dot_general(lhs, rhs, dimension_numbers, precision=None,
                     preferred_element_type=None):
    """``nn.Dense.dot_general`` replacement: int8 x int8 dot against a
    "w8a8" :class:`QuantizedWeight` with dynamic per-row activation
    scales; plain arrays fall through to ``lax.dot_general``."""
    if not isinstance(rhs, QuantizedWeight):
        return jax.lax.dot_general(
            lhs, rhs, dimension_numbers, precision=precision,
            preferred_element_type=preferred_element_type)
    assert rhs.fmt == "w8a8", rhs.fmt
    (lc, rc), (lb, rb) = dimension_numbers
    assert tuple(rc) == (0,) and not lb and not rb, (
        "w8a8 kernels only support Dense-style contractions")
    v, s = rhs.arrays
    sx = jnp.max(jnp.abs(lhs.astype(jnp.float32)), axis=-1,
                 keepdims=True) / 127.0
    # clip before the int8 cast (matching the weight branch): a NaN/inf
    # activation row would otherwise cast to undefined int8 values
    xq = jnp.clip(jnp.round(lhs.astype(jnp.float32) /
                            jnp.maximum(sx, 1e-12)),
                  -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, v, dimension_numbers,
                              preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * sx * s).astype(
        lhs.dtype if jnp.issubdtype(lhs.dtype, jnp.floating)
        else rhs.dtype)


def _dense_supports_promote_dtype() -> bool:
    import inspect

    import flax.linen as nn

    return "promote_dtype" in inspect.signature(nn.Dense).parameters


def _patch_flax_promote_dtype() -> None:
    """flax < 0.10.2 compat: ``nn.Dense`` has no ``promote_dtype``
    attribute there, and its module-level ``promote_dtype`` would
    ``jnp.asarray`` a :class:`QuantizedWeight` kernel.  Wrap that one
    function (idempotently) to pass quantized leaves through — plain
    arrays take the original path unchanged."""
    from flax.linen import dtypes as _dtypes
    from flax.linen import linear as _linear

    if getattr(_linear.promote_dtype, "_dstpu_quant_aware", False):
        return
    orig = _dtypes.promote_dtype

    def promote(*args, dtype=None, **kw):
        qs = [a if isinstance(a, QuantizedWeight) else None for a in args]
        if not any(q is not None for q in qs):
            return orig(*args, dtype=dtype, **kw)
        proms = orig(*(None if q is not None else a
                       for q, a in zip(qs, args)), dtype=dtype, **kw)
        return [q if q is not None else p for q, p in zip(qs, proms)]

    promote._dstpu_quant_aware = True
    _linear.promote_dtype = promote


def weight_quant_dense_kwargs(weight_quant: str):
    """``nn.Dense`` kwargs wiring native quantized-weight consumption
    into a model (the model zoo's ``cfg.weight_quant`` knob)."""
    if weight_quant in (None, "none"):
        return {}
    assert weight_quant == "w8a8", weight_quant
    if not _dense_supports_promote_dtype():
        _patch_flax_promote_dtype()
        return {"dot_general": w8a8_dot_general}
    return {"promote_dtype": quant_promote_dtype,
            "dot_general": w8a8_dot_general}
