"""The fused tick's chunk attention kernel (``ops/paged_prefill.py``), run
through the Pallas interpreter so tier-1 covers it on the CPU, against
``ref_paged_attention``.

Contracts under test:

- **Parity** over every shape a fused tick takes: 8 and 16 KV heads, 4 and
  1 query heads a KV head, chunks that start and end off a page and off a
  row tile, chunks of several row tiles and key blocks, a tick that is no
  whole number of row tiles (its last tile starts early), several prefilling
  sequences in one tick, one-query sequences between them, a window that
  binds inside a chunk, a looped pool's page numbers, ``num_seqs`` below
  the table's rows.
- **Nothing it should not read is read**: page 0, the tail of every last
  page and the pages past a sequence's end hold NaN, the table is padded
  with ``-1``, and the output is finite; rows of no sequence come back
  zero.
- **Separation by** ``cu_q_lens``: :func:`fused_tick_attention` sends the
  one-query sequences to the decode kernel and the others here, records
  its route, and falls back where either layout does not hold.
- **The work list** itself, and the rules that pick tiles and VMEM from
  the shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.paged import ref_paged_attention
from deepspeed_tpu.ops import paged_prefill as pp
from deepspeed_tpu.ops import routes

PAGE, PPS, D = 16, 64, 128
SM = D ** -0.5


def _case(heads, seqs, *, slots=8, pad_rows=5, loop=None, seed=0,
          page=PAGE, min_rows=16):
    """A tick of ``seqs`` = (query rows, kv length) in packed order, over a
    pool in which everything it must not read is NaN.  ``loop`` = (U, u):
    the pool of a looped model, the sequences' pages at ``p * U + u``."""
    H, Hkv = heads
    r = np.random.default_rng(seed)
    n = len(seqs)
    U, u = loop or (1, 0)
    P = 2 + sum(-(-kv // page) for _, kv in seqs)
    pool = np.full((P * U, page, 2 * Hkv, D), np.nan, np.float32)
    table = np.full((slots, PPS), -1, np.int32)
    kv_lens = np.zeros((slots,), np.int32)
    cu = np.zeros((slots + 1,), np.int32)
    perm, k = r.permutation(np.arange(1, P)), 0
    for j, (q_len, kv) in enumerate(seqs):
        need = -(-kv // page)
        mine = perm[k:k + need] * U + u
        k += need
        table[j, :need] = mine
        flat = r.standard_normal((need * page, 2 * Hkv, D))
        flat[kv:] = np.nan                    # a stale row past the end
        pool[mine] = flat.reshape(need, page, 2 * Hkv, D)
        kv_lens[j], cu[j + 1] = kv, cu[j] + q_len
    cu[n + 1:] = cu[n]
    # rows past the last sequence, up to a whole number of sublane tiles
    T = max(-(-(int(cu[n]) + pad_rows) // 16) * 16, min_rows)
    q = jnp.asarray(r.standard_normal((T, H, D)), jnp.bfloat16)
    return (q, jnp.asarray(pool, jnp.bfloat16), jnp.asarray(kv_lens),
            jnp.asarray(table), jnp.asarray(cu), jnp.asarray([n], jnp.int32))


def _reference(case, window):
    """The XLA path over the same pool with the NaN taken out (it masks
    by position and would multiply them by zero)."""
    q, pages, *meta = case
    clean = jnp.nan_to_num(pages.astype(jnp.float32)).astype(pages.dtype)
    return ref_paged_attention(q, clean, *meta, sm_scale=SM,
                               sliding_window=window)


def _chunk(case, *, window, tiles, sm_scale=SM):
    """The kernel alone, which takes and gives heads first."""
    q, *rest = case
    return pp._chunk(q.transpose(1, 0, 2), *rest, sm_scale=sm_scale,
                     sliding_window=window, tiles=tiles,
                     interpret=True).transpose(1, 0, 2)


def _close(got, want, case, least=2):
    cu, n = np.asarray(case[4]), int(case[5][0])
    got, want = (np.asarray(x.astype(jnp.float32)) for x in (got, want))
    assert np.isfinite(got).all(), "something it should not read was read"
    served = np.zeros((got.shape[0],), bool)
    for j in range(n):
        if cu[j + 1] - cu[j] >= least:
            served[cu[j]:cu[j + 1]] = True
    assert (got[~served] == 0).all(), "a row of no sequence was written"
    # one bf16 rounding of values of order 1
    np.testing.assert_allclose(got[served], want[served], atol=1.6e-2,
                               rtol=0)


MISTRAL, OLMOE, NARROW = (32, 8), (16, 16), (8, 2)
# four KV heads: a token PAIR a sublane tile; a query group of seven
SMALLTHINKER = (28, 4)

# (heads, [(query rows, kv length)], window, (tokens a row tile, pages a
# key block), loop)
TICKS = {
    "8kv-4q-a-chunk-off-page-and-off-tile":
        (MISTRAL, [(37, 100)], None, (16, 2), None),
    "16kv-1q-a-chunk-off-page-and-off-tile":
        (OLMOE, [(37, 100)], None, (32, 2), None),
    "8kv-a-chunk-of-several-row-tiles-from-position-0":
        (MISTRAL, [(70, 70)], None, (16, 2), None),
    "16kv-a-chunk-of-several-row-tiles-and-key-blocks":
        (OLMOE, [(75, 230)], None, (32, 4), None),
    "two-prefilling-sequences-in-one-tick":
        (NARROW, [(40, 90), (50, 50)], None, (16, 2), None),
    "one-query-sequences-between-chunks":
        (MISTRAL, [(1, 40), (37, 100), (1, 7), (1, 250), (21, 21)], None,
         (16, 2), None),
    "a-window-that-binds-inside-a-chunk":
        (MISTRAL, [(60, 200), (1, 90)], 24, (16, 2), None),
    "a-window-over-several-key-blocks":
        (OLMOE, [(90, 256)], 100, (32, 2), None),
    "a-looped-pools-pages":
        (OLMOE, [(1, 30), (45, 120)], None, (32, 2), (4, 2)),
    "the-last-page-table-row-full":
        (NARROW, [(30, PAGE * PPS)], None, (16, 16), None),
    "key-blocks-of-four-lane-tiles-a-diagonal-tile-reaches-some-of":
        (NARROW, [(1, 9), (150, 700)], None, (32, 32), None),
    "4kv-7q-a-chunk-off-page-and-off-tile":
        (SMALLTHINKER, [(37, 100)], None, (16, 2), None),
    "4kv-7q-one-query-sequences-between-chunks-of-several-key-blocks":
        (SMALLTHINKER, [(1, 40), (75, 230), (1, 7), (21, 21)], None,
         (16, 4), None),
    "4kv-7q-a-window-that-binds-inside-a-chunk":
        (SMALLTHINKER, [(60, 200), (1, 90)], 24, (16, 2), None),
    "a-tick-of-one-row-tile":
        (NARROW, [(9, 20), (1, 3)], None, (64, 2), None),
}


@pytest.mark.parametrize("name", TICKS)
def test_chunk_kernel_matches_reference(name):
    """Every sequence of two rows or more, with one-query sequences, rows
    past the last sequence and ``num_seqs`` below the table's rows left
    zero."""
    heads, seqs, window, tiles, loop = TICKS[name]
    case = _case(heads, seqs, loop=loop, seed=len(name), min_rows=tiles[0])
    got = _chunk(case, window=window, tiles=tiles)
    _close(got, _reference(case, window), case)


def test_heads_of_64_padded_to_128():
    """``lfm2-24b-a2b-serve``'s: q, K and V zero-padded to the lanes, the
    softmax scale the true head's; the padded columns come back zero."""
    heads, seqs = MISTRAL, [(1, 33), (37, 70)]
    q, pages, *meta = _case(heads, seqs, seed=7)
    lanes = jnp.arange(D) < 64
    q = jnp.where(lanes, q, 0).astype(q.dtype)
    pages = jnp.where(lanes, pages, 0).astype(pages.dtype)   # NaN stays
    case = (q, pages, *meta)
    got = _chunk(case, window=None, tiles=(16, 2), sm_scale=64 ** -0.5)
    clean = jnp.nan_to_num(pages.astype(jnp.float32)).astype(pages.dtype)
    want = ref_paged_attention(q[..., :64], clean[..., :64], *meta,
                               sm_scale=64 ** -0.5)
    assert (np.asarray(got[..., 64:], np.float32) == 0).all()
    _close(got[..., :64], want, case)


def test_four_kv_heads_through_a_table_with_released_columns():
    """A window layer's table as ``window_pages.py`` leaves it behind a
    long prompt: the columns wholly behind the chunk's first row's window
    read -1, their pages hold NaN (another sequence's by now), and the chunk
    kernel (a token PAIR a tile, a group of seven) never fetches them: a
    released column of a key block it does fetch clamps to the trash page,
    whose stale rows are finite and masked."""
    window, seqs, page = 40, [(48, 400), (1, 90), (20, 20)], 64
    q, pages, kv_lens, table, cu, n = _case(SMALLTHINKER, seqs, seed=11,
                                            page=page)
    pool, table = np.array(pages.astype(jnp.float32)), np.array(table)
    for j, (q_len, kv) in enumerate(seqs):
        gone = max(kv - q_len - window + 1, 0) // page
        pool[table[j, :gone]] = np.nan
        table[j, :gone] = -1
    pool[0] = np.random.default_rng(5).standard_normal(pool[0].shape)
    case = (q, jnp.asarray(pool, jnp.bfloat16), kv_lens, jnp.asarray(table),
            cu, n)
    got = _chunk(case, window=window, tiles=(16, 2))
    _close(got, _reference(case, window), case)
    # and both kernels through the one call a fused tick makes
    got = pp.fused_tick_attention(
        *case, sm_scale=SM, sliding_window=window, fallback=None,
        fallback_route="xla-dense", interpret=True)
    _close(got, _reference(case, window), case, least=1)


def test_no_sequence_is_no_work():
    case = list(_case(NARROW, [(5, 9)]))
    case[5] = jnp.asarray([0], jnp.int32)
    got = _chunk(case, window=None, tiles=(16, 2))
    assert (np.asarray(got, np.float32) == 0).all()


def test_work_list_holds_only_blocks_that_are_attended():
    kv_lens = jnp.asarray([300, 999, 64, 65, 1, 500], jnp.int32)
    cu = jnp.asarray([0, 100, 101, 165, 167, 168, 168], jnp.int32)
    kw = dict(page_size=16, pages_per_seq=64, kb=4)
    (seq, block, flags), n = pp.work_list(
        kv_lens, cu, jnp.asarray([5], jnp.int32), sliding_window=None, **kw)
    n = int(n[0])
    assert seq.shape == (6 * 16,) and n == 5 + 1 + 2
    assert seq[:n].tolist() == [0] * 5 + [2] + [3] * 2   # no 1, 4; 5 is past
    assert block[:n].tolist() == [0, 1, 2, 3, 4, 0, 0, 1]
    assert flags[:n].tolist() == [1, 0, 0, 0, 2, 3, 1, 2]
    # a window of 150 keys: sequence 0's first row (position 200) attends
    # keys from 51 on, block 0; of 40 keys: from 161 on, block 2
    for window, first in ((150, 0), (40, 2)):
        (_, block, _), n = pp.work_list(
            kv_lens, cu, jnp.asarray([1], jnp.int32),
            sliding_window=window, **kw)
        assert block[:int(n[0])].tolist() == list(range(first, 5))


@pytest.mark.parametrize("heads,kv_heads,page,d,dtype,want", [
    (32, 8, 64, 128, jnp.bfloat16, (128, 8)),    # mistral-7b-serve, lfm2
    (16, 16, 64, 128, jnp.bfloat16, (256, 8)),   # olmoe, ouro
    (32, 8, 16, 128, jnp.bfloat16, (128, 32)),
    (64, 8, 256, 128, jnp.bfloat16, (128, 2)),   # never under 128 tokens
    (32, 16, 64, 128, jnp.bfloat16, (256, 8)),
    (32, 8, 1024, 128, jnp.bfloat16, (128, 1)),  # never under a page
    (16, 4, 64, 128, jnp.bfloat16, (128, 8)),   # a token PAIR a tile
    (28, 4, 64, 128, jnp.bfloat16, (128, 8)),   # smallthinker: a group of 7
    (28, 4, 1, 128, jnp.bfloat16, None),     # half a pair a page
    (8, 2, 64, 128, jnp.bfloat16, None),     # four tokens a tile: never built
    (12, 8, 64, 128, jnp.bfloat16, None),    # no whole group of heads
    (32, 8, 32, 128, jnp.bfloat16, (128, 16)),
    (32, 8, 64, 64, jnp.bfloat16, None),     # head_dim
    (32, 8, 64, 128, jnp.float32, None),     # K and V are not one word
    (32, 8, 64, 128, jnp.int8, None),
])
def test_tiling_from_the_shapes(heads, kv_heads, page, d, dtype, want):
    assert pp.tiling(heads, kv_heads, page, d, dtype) == want


def test_vmem_is_reckoned_from_the_shapes():
    MiB = 2 ** 20
    # mistral-7b-serve's tick of 544 rows, five row tiles: q and o 4.25
    # each, accumulator 10, three key blocks of 2, eight score tiles of 1
    assert pp.vmem_bytes(544, 32, 8, 64, 128, (128, 8)) == int(
        (4.25 * 2 + 10 + 3 * 2 + 8 * 1) * MiB)
    assert pp.vmem_bytes(544, 32, 8, 64, 128, (128, 8)) < pp._VMEM_CEILING
    assert pp.route_name((128, 8), 32, 8, 64) == "pallas-chunk-512x512"
    assert pp.route_name((256, 8), 16, 16, 64) == "pallas-chunk-256x512"


def test_the_call_separates_by_cu_q_lens_records_its_route_and_falls_back(
        monkeypatch):
    monkeypatch.setattr(routes, "_taken", {})
    heads, seqs, window = MISTRAL, [(1, 40), (37, 100), (1, 7), (21, 21)], 30
    # pages of 64 tokens, as the decode kernel's layout wants at 8 KV heads
    # 67 rows: no whole number of sublane tiles, and under a row tile
    case = _case(heads, seqs, seed=11, page=64)
    case = (case[0][:67], *case[1:])

    def never():
        raise AssertionError("eligible call fell back")

    got = pp.fused_tick_attention(
        *case, sm_scale=SM, sliding_window=window, fallback=never,
        fallback_route="xla-dense", interpret=True)
    _close(got, _reference(case, window), case, least=1)
    assert routes.taken() == {
        pp.KERNEL: "pallas-chunk-512x512-interpret"}
    # no interpreter and no TPU: the path it had, and the route says so
    assert pp.fused_tick_attention(
        *case, sm_scale=SM, fallback=lambda: "fell back",
        fallback_route="xla-dense") == "fell back"
    # nor where a layout does not hold, interpreter or not
    narrow = _case(NARROW, [(9, 20)])
    assert pp.fused_tick_attention(
        *narrow, sm_scale=SM, fallback=lambda: "fell back",
        fallback_route="pallas-ragged", interpret=True) == "fell back"
    assert routes.taken()[pp.KERNEL] == (
        "pallas-chunk-512x512-interpret+xla-dense+pallas-ragged")
