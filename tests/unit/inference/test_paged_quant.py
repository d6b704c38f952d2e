"""Dequant-free quantized paged attention (the kv_quant tentpole's
kernel layer).

Contracts under test:

- **Parity**: the Pallas quantized-pages kernel
  (``ops/ragged_paged_quant.py``, run through the interpreter so tier-1
  covers it on CPU) matches the gathered-pages XLA reference
  (``ref_paged_attention_quant``) bit-for-tolerance on int8 AND fp8
  pools, with sliding windows, -1 page padding, and padded sequence
  slots.
- **Semantics**: both quantized variants match the full-precision
  reference run over a manually dequantized pool — the quantized read
  path changes WHERE dequant happens, never what is computed.
- **No full-pool materialization**: the XLA variant's traced program
  contains no float operand shaped like the whole pool; its dequant
  operand is bounded by the gathered pages (O(attended rows)).
- **Scale epsilon regression**: all-zero and tiny-magnitude rows store
  finite scales, dequantize finite (no inf/nan), and tiny rows survive
  the quantization roundtrip instead of collapsing to zero (the old
  ``max(absmax, 1e-12)`` floor zeroed any row below 1e-12).
"""
import dataclasses
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.paged import (kv_dequant_path,
                                           ref_paged_attention,
                                           ref_paged_attention_quant)
from deepspeed_tpu.models.llama import get_config
from deepspeed_tpu.ops import ragged_paged_attention_quant

CFG = get_config("tinyllama", vocab_size=64, hidden_size=32,
                 intermediate_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=128, dtype=jnp.float32,
                 param_dtype=jnp.float32, scan_layers=False, remat=False,
                 use_flash_attention=False)


def _pool(fmt, P=6, page=8, Hkv=2, D=128, seed=0):
    r = np.random.default_rng(seed)
    scales = (r.random((P, page, 2 * Hkv)) * 0.02 + 0.001).astype(
        np.float32)
    if fmt == "int8":
        pages = jnp.asarray(
            r.integers(-127, 128, size=(P, page, 2 * Hkv, D)), jnp.int8)
    else:
        pages = jnp.asarray(
            np.clip(r.standard_normal((P, page, 2 * Hkv, D)) * 100,
                    -448, 448), jnp.float8_e4m3fn)
    return pages, jnp.asarray(scales)


def _meta(seed=0):
    """Three ragged sequences over a 6-page pool: mid-page lengths,
    -1 page padding, shared q buffer."""
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.standard_normal((12, 4, 128)), jnp.float32)
    kv_lens = jnp.asarray([10, 20, 5], jnp.int32)
    page_indices = jnp.asarray([[1, 2, -1], [3, 4, 5], [2, -1, -1]],
                               jnp.int32)
    cu_q_lens = jnp.asarray([0, 4, 10, 12], jnp.int32)
    num_seqs = jnp.asarray([3], jnp.int32)
    return q, kv_lens, page_indices, cu_q_lens, num_seqs


SM = 1.0 / np.sqrt(128)


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
@pytest.mark.parametrize("window", [None, 7])
def test_pallas_kernel_matches_xla_reference(fmt, window):
    pages, scales = _pool(fmt)
    q, kv_lens, pi, cu, ns = _meta()
    ref = ref_paged_attention_quant(q, pages, scales, kv_lens, pi, cu,
                                    ns, sm_scale=SM, sliding_window=window)
    ker = ragged_paged_attention_quant(q, pages, scales, kv_lens, pi, cu,
                                       ns, sm_scale=SM,
                                       sliding_window=window,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=5e-6)


def test_pallas_kernel_padded_seq_slots():
    """Slots past num_seqs contribute nothing and their q rows are 0,
    exactly like the reference's token_valid mask."""
    pages, scales = _pool("int8")
    q, kv_lens, pi, _, _ = _meta()
    cu = jnp.asarray([0, 4, 10, 10], jnp.int32)    # slot 2 empty
    ns = jnp.asarray([2], jnp.int32)
    ref = ref_paged_attention_quant(q[:10], pages, scales, kv_lens, pi,
                                    cu, ns, sm_scale=SM)
    ker = ragged_paged_attention_quant(q[:10], pages, scales, kv_lens,
                                       pi, cu, ns, sm_scale=SM,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=5e-6)


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
def test_quant_variants_match_full_precision_reference(fmt):
    """Dequantizing the pool by hand and running the full-precision
    reference gives the same answer — the quantized read path moves the
    dequant, it does not change the math."""
    pages, scales = _pool(fmt)
    q, kv_lens, pi, cu, ns = _meta()
    full = pages.astype(jnp.float32) * scales[..., None]
    want = ref_paged_attention(q, full, kv_lens, pi, cu, ns, sm_scale=SM)
    got_ref = ref_paged_attention_quant(q, pages, scales, kv_lens, pi,
                                        cu, ns, sm_scale=SM)
    got_ker = ragged_paged_attention_quant(q, pages, scales, kv_lens, pi,
                                           cu, ns, sm_scale=SM,
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               atol=5e-6)
    np.testing.assert_allclose(np.asarray(got_ker), np.asarray(want),
                               atol=5e-6)


def test_xla_variant_never_materializes_full_pool():
    """The gathered-pages variant's dequant operand is bounded by the
    pages the batch attends (S * pages_per_seq), never the pool: with a
    64-page pool and 4 gathered pages, no float intermediate in the
    traced program leads with the pool dim."""
    P = 64
    pages, scales = _pool("int8", P=P)
    q, kv_lens, pi, cu, ns = _meta()           # gathers 2 slots x 3 pages

    jaxpr = str(jax.make_jaxpr(
        lambda *a: ref_paged_attention_quant(*a, sm_scale=SM))(
        q[:10], pages, scales, kv_lens[:2], pi[:2], cu[:3],
        jnp.asarray([2], jnp.int32)))
    # no full-width [P, page, 2Hkv, D] float anywhere (the fp32 SCALE
    # buffer is pool-shaped by definition but D-free — 4 bytes per row)
    assert not re.search(rf"f32\[{P},\d+,\d+,\d+\]", jaxpr), (
        "full-pool-shaped float operand in the gathered-dequant "
        "program — the dequant must be O(attended pages)")
    # the dequant intermediate IS there, at the gathered size (2x3=6)
    assert re.search(r"f32\[6,\d+,\d+,128\]", jaxpr)
    # the 1-byte pool itself is of course an operand
    assert re.search(rf"i8\[{P},", jaxpr)


def test_head_dim_constraint_and_route():
    pages, scales = _pool("int8", D=64)
    q, kv_lens, pi, cu, ns = _meta()
    with pytest.raises(AssertionError, match="head_dim 128"):
        ragged_paged_attention_quant(q[:, :, :64], pages, scales,
                                     kv_lens, pi, cu, ns, sm_scale=SM,
                                     interpret=True)
    # on this CPU container every head dim routes to the XLA gather
    assert kv_dequant_path(128) in ("pallas-quant", "xla-gather")
    assert kv_dequant_path(64) == "xla-gather"


# -- scale epsilon regression (satellite) --------------------------------


class _Harness(nn.Module):
    cfg: object

    @nn.compact
    def __call__(self, q, k, v, ragged_meta):
        from deepspeed_tpu.inference.paged import paged_update_and_attend

        return paged_update_and_attend(self, q, k, v, ragged_meta,
                                       self.cfg)


def _write_rows(fmt, k, v):
    """Push T=8 rows of K/V through the quant write path; return
    (output, kv_pages, kv_scales)."""
    T, Hkv, D = 8, 2, 16
    cfg = dataclasses.replace(CFG, kv_num_pages=5, kv_page_size=4,
                              kv_cache_dtype=fmt)
    q = jnp.ones((1, 4, T, D), jnp.float32)
    meta = {"kv_lens": jnp.asarray([T], jnp.int32),
            "page_indices": jnp.asarray([[1, 2]], jnp.int32),
            "cu_q_lens": jnp.asarray([0, T], jnp.int32),
            "num_seqs": jnp.asarray([1], jnp.int32),
            "new_kv_dest": jnp.arange(4, 12, dtype=jnp.int32)}
    m = _Harness(cfg)
    vars_ = m.init(jax.random.PRNGKey(0), q, k, v, meta)
    y, mut = m.apply(vars_, q, k, v, meta, mutable=["cache"])
    return (np.asarray(y), np.asarray(mut["cache"]["kv_pages"],
                                      dtype=np.float32),
            np.asarray(mut["cache"]["kv_scales"]))


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
def test_all_zero_rows_store_finite_scales(fmt):
    T, Hkv, D = 8, 2, 16
    z = jnp.zeros((1, Hkv, T, D), jnp.float32)
    y, pages, scales = _write_rows(fmt, z, z)
    assert np.isfinite(y).all()
    assert np.isfinite(scales).all() and (scales >= 0).all()
    # written rows carry the normal-f32 floor, never a zero or
    # subnormal scale whose reciprocal could overflow the store cast
    written = scales.reshape(-1, 2 * Hkv)[4:12]
    assert (written >= np.finfo(np.float32).tiny).all()
    # zero rows dequantize to exact zero
    np.testing.assert_array_equal(pages.reshape(-1, 2 * Hkv, D)[4:12], 0)


@pytest.mark.parametrize("fmt,tol", [("int8", 0.02), ("fp8", 0.08)])
def test_tiny_magnitude_rows_survive_roundtrip(fmt, tol):
    """Rows at 1e-30 round-trip with normal relative error.  The old
    ``max(absmax, 1e-12)`` floor forced their effective scale 18 orders
    of magnitude too big, quantizing every element to zero."""
    T, Hkv, D = 8, 2, 16
    r = np.random.default_rng(5)
    k = jnp.asarray(r.standard_normal((1, Hkv, T, D)) * 1e-30,
                    jnp.float32)
    v = jnp.asarray(r.standard_normal((1, Hkv, T, D)) * 1e-30,
                    jnp.float32)
    y, pages, scales = _write_rows(fmt, k, v)
    assert np.isfinite(y).all()
    assert np.isfinite(scales).all()
    deq = (pages.reshape(5 * 4, 2 * Hkv, D)[4:12] *
           scales.reshape(5 * 4, 2 * Hkv)[4:12, :, None])
    # rows land in pages [T, 2Hkv, D]-flat in (k, v) interleaved order
    want = np.stack([np.asarray(k)[0].transpose(1, 0, 2),
                     np.asarray(v)[0].transpose(1, 0, 2)],
                    axis=2).reshape(T, 2 * Hkv, D)
    rel = np.abs(deq - want).max() / np.abs(want).max()
    assert rel < tol, f"{fmt}: tiny rows lost to quantization ({rel})"
    assert np.abs(deq).max() > 0, "rows collapsed to zero (old floor)"


def test_pallas_kernel_blocks_over_query_rows():
    """More query rows than one VMEM block (128): sequences that span, end
    in and start in different blocks, and padded tail rows."""
    r = np.random.default_rng(2)
    pages, scales = _pool("int8", P=40, page=8)
    T = 300
    q = jnp.asarray(r.standard_normal((T, 4, 128)), jnp.float32)
    kv_lens = jnp.asarray([150, 160, 20], jnp.int32)
    pi = np.full((3, 20), -1, np.int32)
    pi[0, :19] = np.arange(1, 20)
    pi[1, :20] = np.arange(20, 40)
    pi[2, :3] = [5, 6, 7]
    cu = jnp.asarray([0, 140, 290, 300], jnp.int32)
    ns = jnp.asarray([3], jnp.int32)
    ref = ref_paged_attention_quant(q, pages, scales, kv_lens,
                                    jnp.asarray(pi), cu, ns, sm_scale=SM)
    ker = ragged_paged_attention_quant(q, pages, scales, kv_lens,
                                       jnp.asarray(pi), cu, ns, sm_scale=SM,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=5e-6)
