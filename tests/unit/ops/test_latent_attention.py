"""``ops/latent_attention.py``'s two kernels under the Pallas interpreter
against their XLA twin (``inference/paged.py:ref_latent_paged_attention``)
and against the EXPANDED form computed densely: per-head keys and values
made from the cached rows by the up-projection, which is what the absorbed
form must equal.

float32 throughout (the interpreter takes any dtype), so the tolerance is
rounding alone: 2e-5 on outputs of order 1 (another order of summation over
up to 150 keys).  The bf16 case goes through the public entry points at the
tiling their rule gives and holds 3e-2, bf16's own step on sums of order 1.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.paged import ref_latent_paged_attention
from deepspeed_tpu.ops import latent_attention as la
from deepspeed_tpu.ops import routes

PAGE, PP, S, H, RANK, W = 16, 10, 4, 3, 128, 256
P = 1 + S * PP
SCALE = 0.11


def _pool(dtype, seed=0):
    """A pool whose every row is live data, q for ``T`` rows, and a page
    table that hands slot ``s`` its pages out of order."""
    rng = np.random.default_rng(seed)
    pages = rng.normal(size=(P, PAGE, W)).astype(np.float32)
    pages[..., RANK + 64:] = 0            # the row's padding columns
    table = rng.permutation(np.arange(1, P)).reshape(S, PP).astype(np.int32)
    return jnp.asarray(pages, dtype), table


def _dense(q, pages, table, seq, pos):
    """Row by row, over its sequence's first ``pos + 1`` rows: the absorbed
    sum, and the same through an up-projection expanded per head."""
    rows = np.asarray(pages, np.float32)[table[seq]].reshape(-1, W)[:pos + 1]
    s = np.einsum("hw,kw->hk", np.asarray(q, np.float32), rows) * SCALE
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return p @ rows[:, :RANK]


def test_the_absorbed_sum_is_the_expanded_attention():
    """``(W_uk^T q_nope) . c = q_nope . (W_uk c)`` and ``W_uv sum p c = sum
    p (W_uv c)``: the identity the kernels rest on, in numbers."""
    rng = np.random.default_rng(3)
    nope, v_dim, rope, n = 24, 20, 8, 37
    c, k_r = rng.normal(size=(n, RANK)), rng.normal(size=(n, rope))
    w_uk, w_uv = (rng.normal(size=(RANK, H, d)) / np.sqrt(RANK)
                  for d in (nope, v_dim))
    q_nope, q_rope = rng.normal(size=(H, nope)), rng.normal(size=(H, rope))
    # expanded: per-head keys and values
    k_nope, v = np.einsum("nc,chd->nhd", c, w_uk), np.einsum(
        "nc,chd->nhd", c, w_uv)
    s = (np.einsum("hd,nhd->hn", q_nope, k_nope) + q_rope @ k_r.T) * SCALE
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hn,nhd->hd", p, v)
    # absorbed, through the twin over a one-sequence pool
    q = np.zeros((1, H, W), np.float32)
    q[0, :, :RANK] = np.einsum("hd,chd->hc", q_nope, w_uk)
    q[0, :, RANK:RANK + rope] = q_rope
    pages = np.zeros((4, PAGE, W), np.float32)
    flat = pages.reshape(-1, W)
    flat[PAGE:PAGE + n, :RANK], flat[PAGE:PAGE + n, RANK:RANK + rope] = c, k_r
    o_c = ref_latent_paged_attention(
        jnp.asarray(q), jnp.asarray(pages), jnp.asarray([n], jnp.int32),
        jnp.asarray([[1, 2, 3]], jnp.int32), jnp.asarray([0, 1], jnp.int32),
        jnp.asarray([1], jnp.int32), rank=RANK, sm_scale=SCALE)
    got = np.einsum("hc,chd->hd", np.asarray(o_c[0]), w_uv)
    assert np.abs(got - want).max() < 2e-5


@pytest.mark.parametrize("group", [1, 4], ids=["a-page-a-group", "four"])
def test_decode_kernel_matches_the_twin_and_the_dense_sum(group):
    pages, table = _pool(jnp.float32)
    kv_lens = np.asarray([1, 150, 0, 33], np.int32)
    live = np.asarray([True, True, False, True])
    q = jnp.asarray(np.random.default_rng(1).normal(size=(S, H, W)),
                    jnp.float32)
    got = np.asarray(la._decode(
        q, pages, jnp.asarray(kv_lens), jnp.asarray(table), jnp.asarray(live),
        rank=RANK, sm_scale=SCALE, group=group, interpret=True))
    assert got.shape == (S, H, RANK) and not got[2].any()
    for s in (0, 1, 3):
        want = _dense(q[s], pages, table, s, kv_lens[s] - 1)
        assert np.abs(got[s] - want).max() < 2e-5
    twin = np.asarray(ref_latent_paged_attention(
        q, pages, jnp.asarray(kv_lens), jnp.asarray(table),
        jnp.arange(S + 1, dtype=jnp.int32), jnp.asarray([S], jnp.int32),
        rank=RANK, sm_scale=SCALE))
    assert np.abs(got[live] - twin[live]).max() < 2e-5


# packed sequences of a tick: (rows, first position); a one-row sequence is
# the decode kernel's and the chunk kernel leaves its row zero
TICKS = {
    "one-chunk-from-zero": [(40, 0)],
    "chunk-continuing-over-pages": [(21, 77)],
    "decode-row-chunk-start-chunk-continue": [(1, 36), (9, 0), (30, 50)],
    "two-chunks-share-a-tile": [(5, 3), (7, 100), (1, 0)],
}


@pytest.mark.parametrize("tick", list(TICKS), ids=list(TICKS))
@pytest.mark.parametrize("rt,kb", [(16, 1), (32, 4)],
                         ids=["tile16-block16", "tile32-block64"])
def test_chunk_kernel_matches_the_dense_sum(tick, rt, kb):
    pages, table = _pool(jnp.float32, seed=2)
    T = 48
    cu, kv_lens = [0], []
    for n, p0 in TICKS[tick]:
        cu.append(cu[-1] + n)
        kv_lens.append(p0 + n)
    n_seqs = len(kv_lens)
    cu += [cu[-1]] * (S - n_seqs)
    kv_lens += [0] * (S - n_seqs)
    q = jnp.asarray(np.random.default_rng(5).normal(size=(H, T, W)),
                    jnp.float32)
    got = np.asarray(la._chunk(
        q, pages, jnp.asarray(kv_lens, jnp.int32), jnp.asarray(table),
        jnp.asarray(cu, jnp.int32), jnp.asarray([n_seqs], jnp.int32),
        rank=RANK, sm_scale=SCALE, rt=rt, kb=kb, interpret=True))
    assert got.shape == (H, T, RANK)
    for j, (n, p0) in enumerate(TICKS[tick]):
        for i in range(n):
            t = cu[j] + i
            if n == 1:
                assert not got[:, t].any()
                continue
            want = _dense(q[:, t], pages, table, j, p0 + i)
            assert np.abs(got[:, t] - want).max() < 2e-5, (j, i)
    assert not got[:, cu[n_seqs]:].any()        # the tick's padding rows


def test_a_fused_tick_in_bf16_takes_both_kernels_at_the_rules_tiling():
    """The public entry point, interpreted, at the tiling its rule gives
    (row tiles of 128 tokens, so the last of a 144-row tick starts early):
    chunk rows from the chunk kernel, one-query rows from the decode
    kernel, both recorded; bf16 against the float32 twin."""
    routes._taken.pop(la.KERNEL_PREFILL, None)
    pages, table = _pool(jnp.bfloat16, seed=4)
    T = 144
    rows = [(1, 90), (130, 20), (1, 5), (6, 0)]
    cu = np.concatenate([[0], np.cumsum([n for n, _ in rows])]
                        ).astype(np.int32)
    kv_lens = np.asarray([p0 + n for n, p0 in rows], np.int32)
    q = jnp.asarray(np.random.default_rng(6).normal(size=(T, H, W)) * 0.3,
                    jnp.bfloat16)
    ragged = (jnp.asarray(kv_lens), jnp.asarray(table), jnp.asarray(cu),
              jnp.asarray([S], jnp.int32))
    twin = lambda dt: ref_latent_paged_attention(   # noqa: E731
        q.astype(dt), pages.astype(dt), *ragged, rank=RANK, sm_scale=SCALE)
    got = la.fused_tick_attention(
        q, pages, *ragged, rank=RANK, sm_scale=SCALE,
        fallback=lambda: twin(jnp.bfloat16), interpret=True)
    assert routes.taken()[la.KERNEL_PREFILL] == (
        f"pallas-latent-chunk-{H * 128}x512-interpret")
    want = np.asarray(twin(jnp.float32))
    got = np.asarray(got.astype(jnp.float32))
    assert got.shape == (T, H, RANK)
    assert np.abs(got[:138] - want[:138]).max() < 3e-2
    assert not got[138:].any()


def test_the_rule_gives_no_tiling_where_the_layout_does_not_hold():
    bf = jnp.bfloat16
    assert la.tiling(64, 258, 640, 512, bf) == (16, 128, 8)
    assert la.tiling(64, 4, 640, 512, bf) == (4, 128, 8)
    assert la.tiling(64, 258, 640, 512, jnp.float32) is None
    assert la.tiling(64, 258, 576, 512, bf) is None     # rows of whole tiles
    assert la.tiling(24, 258, 640, 512, bf) is None
    # at the published widths a chunk of 512 fits VMEM and one of 2,048
    # does not (q and the output lie whole in it)
    assert la.chunk_vmem_bytes(528, 20, 640, 512, 128, 512) \
        < la._VMEM_CEILING < la.chunk_vmem_bytes(2064, 20, 640, 512, 128, 512)


def test_a_float32_call_takes_the_twin_and_says_so():
    routes._taken.pop(la.KERNEL_DECODE, None)
    pages, table = _pool(jnp.float32)
    q = jnp.zeros((S, H, W), jnp.float32)
    out = la.decode_attention(
        q, pages, jnp.ones((S,), jnp.int32), jnp.asarray(table), None,
        rank=RANK, sm_scale=SCALE, fallback=lambda: "the twin")
    assert out == "the twin"
    assert routes.taken()[la.KERNEL_DECODE] == la.XLA_ROUTE
