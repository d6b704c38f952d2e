"""``ops/latent_attention.py``'s two kernels under the Pallas interpreter
against their XLA twin (``inference/paged/latent.py:ref_latent_paged_attention``)
and against the EXPANDED form computed densely: per-head keys and values
made from the cached rows by the up-projection, which is what the absorbed
form must equal.

float32 where the kernel is called directly (the interpreter takes any
dtype), so the tolerance is rounding alone: 2e-5 on outputs of order 1
(another order of summation over up to 1,100 keys).  The bf16 cases go
through the public entry point at the tiling its rule gives (row tiles of
128 tokens against blocks of 512 keys) and hold 0.0156, bf16's own step on
sums of order 1.

The chunk kernel takes q HEADS FIRST (``[H, Tp, W]``, the tick's rows in
whole sublane tiles) and answers ``[H, Tp, rank]``, the layout the
up-projection reads (``ops/latent_attention.py``).
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
# the rule's tiling at pages of 16: 128 tokens a row tile, 32 pages (512
# keys, four lane tiles) a block; a pool of eight slots of 1,152 tokens
RULE_RT, RULE_KB, LONG_PP, LONG_S = 128, 32, 72, 8


def _pool(dtype, seed=0, slots=S, pp=PP):
    """A pool whose every row is live data, and a page table that hands slot
    ``s`` its pages out of order."""
    rng = np.random.default_rng(seed)
    n = 1 + slots * pp
    pages = rng.normal(size=(n, PAGE, W)).astype(np.float32)
    pages[..., RANK + 64:] = 0            # the row's padding columns
    table = rng.permutation(np.arange(1, n)).reshape(slots, pp
                                                     ).astype(np.int32)
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
# at the rule's tiling.  The cut of the last block: a chunk whose last row
# lies in each of the four lane tiles of its second block, and one whose
# rows lie on both sides of a block's edge (the first block masked whole,
# the second cut at its first lane tile).  The packing: a chunk that starts
# mid-tile behind one and behind seven one-query rows.  A tick that is no
# whole number of row tiles (300 rows in three tiles).  A tile that answers
# for a sublane tile of rows or fewer takes the narrow copy of the code: the
# rows a chunk behind one-query rows spills into its last tile (above), and
# eight rows at a tile's END behind another chunk
LONG_TICKS = {
    "last-row-in-lane-tile-1": [(40, 572)],
    "last-row-in-lane-tile-2": [(40, 662)],
    "last-row-in-lane-tile-3": [(40, 812)],
    "last-row-in-lane-tile-4": [(40, 932)],
    "rows-on-both-sides-of-a-block-edge": [(100, 460)],
    "chunk-behind-one-query-row": [(1, 30), (130, 600)],
    "chunk-behind-seven-query-rows": [(1, 30 + i) for i in range(7)]
    + [(130, 600)],
    "tick-of-300-rows-in-three-tiles": [(170, 20), (1, 9), (129, 700)],
    "few-rows-at-a-tiles-end": [(120, 0), (100, 520)],
}
# rows a tick is padded to, where its edge needs no width of its own: the
# eight rows at a tile's end lie in the first of three tiles as they lie in
# the first of two, and every distinct width is a program to compile
PADDED_TO = {"few-rows-at-a-tiles-end": 300}
CASES = ([(tick, rt, kb) for tick in TICKS for rt, kb in ((16, 1), (32, 4))]
         + [(tick, RULE_RT, RULE_KB) for tick in LONG_TICKS])
ALL_TICKS = {**TICKS, **LONG_TICKS}


def _packed(tick, slots):
    """``(cu_q_lens, kv_lens, sequences)`` of a tick over ``slots`` slots."""
    cu, kv_lens = [0], []
    for n, p0 in tick:
        cu.append(cu[-1] + n)
        kv_lens.append(p0 + n)
    n_seqs = len(kv_lens)
    cu += [cu[-1]] * (slots - n_seqs)
    kv_lens += [0] * (slots - n_seqs)
    return (np.asarray(cu, np.int32), np.asarray(kv_lens, np.int32), n_seqs)


def _first_rows(q, cu):
    """``q_one [S, H, W]``: the first row of every packed sequence, tokens
    first, as the module hands the decode kernel its queries."""
    return q[:, np.minimum(cu[:-1], q.shape[1] - 1)].transpose(1, 0, 2)


def _stale(pages, table, kv_lens):
    """The pool with NaN in every row a sequence's pages hold past its
    end."""
    pages = np.array(pages, np.float32)
    for s, n in enumerate(kv_lens):
        rows = pages[table[s]].reshape(-1, W)
        rows[n:] = np.nan
        pages[table[s]] = rows.reshape(-1, PAGE, W)
    return pages


@pytest.mark.parametrize("stale", [False, True],
                         ids=["live-pool", "stale-nan"])
@pytest.mark.parametrize("tick,rt,kb", CASES, ids=[
    f"{tick}-tile{rt}-block{kb * PAGE}" for tick, rt, kb in CASES])
def test_chunk_kernel_matches_the_dense_sum(tick, rt, kb, stale):
    """float32, the kernel itself: every row of every chunk against the
    dense sum over its own keys; the rows of one-query sequences, of the
    tick's padding and of the growth to whole tiles zero.  ``stale-nan``:
    the rows a sequence's pages hold PAST ITS END are NaN (what a freed
    page's next owner finds); no answer may see them."""
    long = tick in LONG_TICKS
    slots, pp = (LONG_S, LONG_PP) if long else (S, PP)
    pages, table = _pool(jnp.float32, seed=2, slots=slots, pp=pp)
    cu, kv_lens, n_seqs = _packed(ALL_TICKS[tick], slots)
    T = max(int(cu[-1]), PADDED_TO.get(tick, 0)) + 3    # rows of padding
    Tp = max(-(-T // 16) * 16, rt)
    q = jnp.asarray(np.random.default_rng(5).normal(size=(H, Tp, W)),
                    jnp.float32)
    pool = jnp.asarray(_stale(pages, table, kv_lens)) if stale else pages
    got = np.asarray(la._chunk(
        q, pool, jnp.asarray(kv_lens), jnp.asarray(table), jnp.asarray(cu),
        jnp.asarray([n_seqs], jnp.int32), rank=RANK, sm_scale=SCALE, rt=rt,
        kb=kb, interpret=True))
    assert got.shape == (H, Tp, RANK)
    for j, (n, p0) in enumerate(ALL_TICKS[tick]):
        for i in range(n):
            t = cu[j] + i
            if n == 1:
                assert not got[:, t].any()
                continue
            want = _dense(q[:, t], pages, table, j, p0 + i)
            assert np.abs(got[:, t] - want).max() < 2e-5, (j, i)
    assert not got[:, cu[n_seqs]:].any()        # padding and growth


@pytest.mark.parametrize("tick", list(LONG_TICKS), ids=list(LONG_TICKS))
def test_a_fused_tick_in_bf16_matches_the_twin(tick):
    """The public entry point in bf16, interpreted, at the rule's tiling,
    with stale NaN rows past every sequence's end: the chunk's rows and
    the one-query rows against the float32 twin over the clean pool, at
    bf16's own step."""
    pages, table = _pool(jnp.bfloat16, seed=7, slots=LONG_S, pp=LONG_PP)
    cu, kv_lens, n_seqs = _packed(LONG_TICKS[tick], LONG_S)
    T = max(int(cu[-1]), PADDED_TO.get(tick, 0)) + 5
    Tp = la.tick_rows(T)
    q = jnp.asarray(np.random.default_rng(8).normal(size=(H, Tp, W)) * 0.3,
                    jnp.bfloat16)
    ragged = (jnp.asarray(kv_lens), jnp.asarray(table), jnp.asarray(cu),
              jnp.asarray([n_seqs], jnp.int32))
    stale = jnp.asarray(_stale(pages, table, kv_lens), jnp.bfloat16)
    out, rows, at = la.fused_tick_attention(
        q, _first_rows(q, cu), stale, *ragged, rank=RANK, sm_scale=SCALE,
        fallback=lambda _: pytest.fail("the twin was taken"), interpret=True)
    assert out.shape == (H, Tp, RANK) and rows.shape == (LONG_S, H, RANK)
    want = np.asarray(ref_latent_paged_attention(
        q.transpose(1, 0, 2).astype(jnp.float32),
        pages.astype(jnp.float32), *ragged, rank=RANK, sm_scale=SCALE))
    got = np.asarray(out.astype(jnp.float32)).transpose(1, 0, 2)
    rows, at = np.asarray(rows.astype(jnp.float32)), np.asarray(at)
    for j, (n, _) in enumerate(LONG_TICKS[tick]):
        if n == 1:
            assert at[j] == cu[j] and not got[cu[j]].any()
            assert np.abs(rows[j] - want[cu[j]]).max() < 0.0156
        else:
            assert at[j] == Tp
            assert np.abs(got[cu[j]:cu[j + 1]]
                          - want[cu[j]:cu[j + 1]]).max() < 0.0156
    assert (at[n_seqs:] == Tp).all() and not got[cu[n_seqs]:].any()


def test_a_fused_tick_in_bf16_takes_both_kernels_at_the_rules_tiling():
    """The public entry point, interpreted, at the tiling its rule gives
    (row tiles of 128 tokens, so the last of a 144-row tick starts early):
    chunk rows from the chunk kernel, one-query rows from the decode kernel,
    both recorded; bf16 against the float32 twin."""
    routes._taken.pop(la.KERNEL_PREFILL, None)
    # the long pool's shapes: the program the 144-row ticks above compiled
    pages, table = _pool(jnp.bfloat16, seed=4, slots=LONG_S, pp=LONG_PP)
    T = 144
    Tp = la.tick_rows(T)
    cu, kv_lens, n_seqs = _packed([(1, 90), (130, 20), (1, 5), (6, 0)],
                                  LONG_S)
    q = jnp.asarray(np.random.default_rng(6).normal(size=(H, Tp, W)) * 0.3,
                    jnp.bfloat16)
    ragged = (jnp.asarray(kv_lens), jnp.asarray(table), jnp.asarray(cu),
              jnp.asarray([n_seqs], jnp.int32))
    twin = lambda q, dt: ref_latent_paged_attention(   # noqa: E731
        q.astype(dt), pages.astype(dt), *ragged, rank=RANK, sm_scale=SCALE)
    out, ones, at = la.fused_tick_attention(
        q, _first_rows(q, cu), pages, *ragged, rank=RANK, sm_scale=SCALE,
        fallback=lambda q: twin(q, jnp.bfloat16), interpret=True)
    assert routes.taken()[la.KERNEL_PREFILL] == (
        f"pallas-latent-chunk-{H * 128}x512-interpret")
    assert Tp == 144 and out.shape == (H, Tp, RANK)
    assert list(np.asarray(at)) == [0, Tp, 131, Tp] + [Tp] * (LONG_S - 4)
    want = np.asarray(twin(q.transpose(1, 0, 2), jnp.float32))
    got = np.array(out.astype(jnp.float32)).transpose(1, 0, 2)
    got[[0, 131]] = np.asarray(ones.astype(jnp.float32))[[0, 2]]
    assert np.abs(got[:138] - want[:138]).max() < 0.0156
    assert not got[138:].any()


def test_a_fused_tick_off_the_kernels_layout_takes_the_twin_heads_first():
    """A float32 call: the twin, handed q tokens first, its answer turned
    to the layout the kernel answers in and every row in it (no one-query
    rows beside it)."""
    routes._taken.pop(la.KERNEL_PREFILL, None)
    pages, table = _pool(jnp.float32, seed=4)
    rows = [(1, 90), (30, 20)]
    cu, kv_lens, n_seqs = _packed(rows, S)
    Tp = 128
    q = jnp.asarray(np.random.default_rng(9).normal(size=(H, Tp, W)),
                    jnp.float32)
    ragged = (jnp.asarray(kv_lens), jnp.asarray(table), jnp.asarray(cu),
              jnp.asarray([n_seqs], jnp.int32))
    twin = lambda q: ref_latent_paged_attention(       # noqa: E731
        q, pages, *ragged, rank=RANK, sm_scale=SCALE)
    out, ones, at = la.fused_tick_attention(
        q, _first_rows(q, cu), pages, *ragged, rank=RANK, sm_scale=SCALE,
        fallback=twin, interpret=True)
    assert routes.taken()[la.KERNEL_PREFILL] == la.XLA_ROUTE
    assert (np.asarray(at) == Tp).all() and not np.asarray(ones).any()
    want = np.asarray(twin(q.transpose(1, 0, 2)))
    assert np.abs(np.asarray(out).transpose(1, 0, 2) - want).max() < 1e-6
    for t, (j, pos) in ((0, (0, 90)), (17, (1, 36))):
        assert np.abs(np.asarray(out)[:, t]
                      - _dense(q[:, t], pages, table, j, pos)).max() < 2e-5


def test_the_rule_gives_no_tiling_where_the_layout_does_not_hold():
    bf = jnp.bfloat16
    assert la.tiling(64, 258, 640, 512, bf) == (16, 128, 8)
    assert la.tiling(64, 4, 640, 512, bf) == (4, 128, 8)
    assert la.tiling(64, 258, 640, 512, jnp.float32) is None
    assert la.tiling(64, 258, 576, 512, bf) is None     # rows of whole tiles
    assert la.tiling(24, 258, 640, 512, bf) is None
    # at the published widths a chunk of 1,024 and one of 2,048 fit VMEM
    # (the output lies whole in it, of q two row tiles: 50 and 70 MiB) and
    # one of 4,096 does not
    need = lambda T: la.chunk_vmem_bytes(T, 20, 640, 512, 128, 512)  # noqa
    assert need(1040) < need(2064) < la._VMEM_CEILING < need(4112)
    assert (need(1040) >> 20, need(2064) >> 20) == (50, 70)


def test_a_float32_call_takes_the_twin_and_says_so():
    routes._taken.pop(la.KERNEL_DECODE, None)
    pages, table = _pool(jnp.float32)
    q = jnp.zeros((S, H, W), jnp.float32)
    out = la.decode_attention(
        q, pages, jnp.ones((S,), jnp.int32), jnp.asarray(table), None,
        rank=RANK, sm_scale=SCALE, fallback=lambda: "the twin")
    assert out == "the twin"
    assert routes.taken()[la.KERNEL_DECODE] == la.XLA_ROUTE
