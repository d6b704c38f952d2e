"""The decode tick's paged attention kernel (``ops/paged_decode.py``),
run through the Pallas interpreter so tier-1 covers it on the CPU,
against ``ref_paged_attention``.

Contracts under test:

- **Parity** at Mistral's, OLMoE's and a narrow model's head counts over
  contexts on both sides of a page boundary and a full page-table row,
  with groups of two pages so that a long sequence takes several.
- **Nothing it should not read is read**: page 0, the pages an idle
  slot's row names and the tail of every last page hold NaN, the table
  is padded with ``-1``, and the output is finite; idle slots come back
  zero.
- **A binding sliding window**: pages wholly before it are in no group.
- **The work list** itself, and the rule that picks group and step from
  the shapes (:func:`tiling`), whose None sends a call back to the path
  it had.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.paged import ref_paged_attention
from deepspeed_tpu.ops import paged_decode as pd
from deepspeed_tpu.ops import routes

PAGE, PPS, D = 64, 8, 128
SM = D ** -0.5


def _case(heads, lens, live, seed=0):
    """A pool in which everything a decode tick must not read is NaN:
    page 0, idle slots' pages, last pages' tails; rows padded with -1."""
    H, Hkv = heads
    r = np.random.default_rng(seed)
    S = len(lens)
    live = np.asarray(live, bool)
    P = 1 + sum(-(-n // PAGE) for n in lens)
    pool = r.standard_normal((P, PAGE, 2 * Hkv, D)).astype(np.float32)
    table = np.full((S, PPS), -1, np.int32)
    perm, k = r.permutation(np.arange(1, P)), 0
    for s, n in enumerate(lens):
        need = -(-n // PAGE)
        table[s, :need] = perm[k:k + need]
        k += need
        if live[s]:
            pool[table[s, need - 1], n - (need - 1) * PAGE:] = np.nan
        else:
            pool[table[s, :need]] = np.nan
    pool[0] = np.nan
    q = jnp.asarray(r.standard_normal((S, H, D)), jnp.bfloat16)
    return (q, jnp.asarray(pool, jnp.bfloat16), jnp.asarray(lens, jnp.int32),
            jnp.asarray(table), jnp.asarray(live))


def _reference(q, pages, kv_lens, table, live, window):
    """The XLA path over the same pool with the NaN taken out (it masks
    by position and would multiply them by zero)."""
    S = q.shape[0]
    clean = jnp.nan_to_num(pages.astype(jnp.float32)).astype(pages.dtype)
    return ref_paged_attention(
        q, clean, jnp.where(live, kv_lens, 1), table,
        jnp.arange(S + 1, dtype=jnp.int32), jnp.asarray([S], jnp.int32),
        sm_scale=SM, sliding_window=window)


def _check(case, window, tiles):
    q, pages, kv_lens, table, live = case
    got = pd._decode(q, pages, kv_lens, table, live, sm_scale=SM,
                     sliding_window=window, tiles=tiles, interpret=True)
    want = _reference(q, pages, kv_lens, table, live, window)
    got, want = (np.asarray(x.astype(jnp.float32)) for x in (got, want))
    live = np.asarray(live)
    assert np.isfinite(got).all(), "something it should not read was read"
    assert (got[~live] == 0).all(), "an idle slot's row was written"
    # one bf16 rounding of values of order 1
    np.testing.assert_allclose(got[live], want[live], atol=1.6e-2, rtol=0)


HEADS = [pytest.param((32, 8), id="mistral-32q-8kv"),
         pytest.param((16, 16), id="olmoe-16q-16kv"),
         pytest.param((8, 2), id="narrow-8q-2kv"),
         # a token pair a sublane tile, a query group of seven
         pytest.param((28, 4), id="smallthinker-28q-4kv")]


@pytest.mark.parametrize("context", [1, 12, 63, 64, 65, PAGE * PPS])
@pytest.mark.parametrize("heads", HEADS)
def test_decode_kernel_matches_reference(heads, context):
    """Live slots at ``context`` and at 150 tokens with idle slots
    between them and at both ends."""
    lens = [70, context, 200, 150, 3, context]
    live = [False, True, False, True, False, True]
    _check(_case(heads, lens, live, seed=context), None, tiles=(2, 1))


@pytest.mark.parametrize("window", [40, 64, 200])
@pytest.mark.parametrize("heads", HEADS)
def test_a_binding_sliding_window(heads, window):
    lens = [PAGE * PPS, 12, 300, 65, 129]
    live = [True, True, False, True, True]
    _check(_case(heads, lens, live, seed=window), window, tiles=(4, 2))


@pytest.mark.parametrize("window", [None, 100, 200])
def test_four_kv_heads_through_a_table_with_released_columns(window):
    """``smallthinker-21b-a3b-serve``'s head counts through a window layer's
    table as ``window_pages.py`` leaves it: the columns wholly behind a live
    slot's window read -1 and their pages hold NaN (another sequence's by
    now), and the step's pages are multiplied a token PAIR a tile."""
    lens = [PAGE * PPS, 12, 300, 65, 450]
    live = [True, True, False, True, True]
    q, pages, kv_lens, table, live_ = _case((28, 4), lens, live, seed=3)
    pool, table = np.array(pages.astype(jnp.float32)), np.array(table)
    for s, n in enumerate(lens):
        gone = max(n - window, 0) // PAGE if window else 0
        pool[table[s, :gone]] = np.nan
        table[s, :gone] = -1
    case = (q, jnp.asarray(pool, jnp.bfloat16), kv_lens, jnp.asarray(table),
            live_)
    _check(case, window, tiles=(4, 2))


def test_work_list_holds_only_pages_that_are_attended():
    kv_lens = jnp.asarray([300, 999, 64, 65, 1], jnp.int32)
    live = jnp.asarray([True, False, True, True, True])
    (slot, page0, count, flags), n = pd.work_list(
        kv_lens, live, page_size=64, pages_per_seq=8, group=2,
        sliding_window=None)
    n = int(n[0])
    assert slot.shape == (5 * 4,) and n == 3 + 1 + 1 + 1
    assert slot[:n].tolist() == [0, 0, 0, 2, 3, 4]          # no slot 1
    assert page0[:n].tolist() == [0, 2, 4, 0, 0, 0]
    assert count[:n].tolist() == [2, 2, 1, 1, 2, 1]
    assert flags[:n].tolist() == [1, 0, 2, 3, 3, 3]
    # a window of 100 keys: slot 0 starts at the page of key 200
    (slot, page0, count, flags), n = pd.work_list(
        kv_lens, live, page_size=64, pages_per_seq=8, group=2,
        sliding_window=100)
    assert page0[:int(n[0])].tolist() == [3, 0, 0, 0]
    assert count[:int(n[0])].tolist() == [2, 1, 2, 1]
    # a length past the table's row (a slot about to stop) is clamped
    (_, page0, count, _), n = pd.work_list(
        jnp.asarray([8 * 64 + 5], jnp.int32), jnp.asarray([True]),
        page_size=64, pages_per_seq=8, group=8, sliding_window=None)
    assert (int(n[0]), int(page0[0]), int(count[0])) == (1, 0, 8)


def test_no_live_slot_is_no_work():
    case = _case((8, 2), [70, 3], [False, False])
    _check(case, None, tiles=(2, 2))


@pytest.mark.parametrize("kv_heads,page,pps,d,dtype,want", [
    (8, 64, 64, 128, jnp.bfloat16, (4, 4)),    # mistral-7b-serve: 1 MiB
    (16, 64, 64, 128, jnp.bfloat16, (2, 2)),   # olmoe-1b-7b-serve: 1 MiB
    (8, 64, 2, 128, jnp.bfloat16, (4, 4)),     # whole steps, if past a row
    (8, 16, 64, 128, jnp.bfloat16, (16, 16)),  # 2,048 rows a step
    (8, 256, 16, 128, jnp.bfloat16, (1, 1)),
    (32, 64, 64, 128, jnp.bfloat16, (1, 1)),   # never under a page
    (4, 64, 64, 128, jnp.bfloat16, (8, 8)),    # a token PAIR a sublane tile
    (4, 64, 129, 128, jnp.bfloat16, (8, 8)),   # smallthinker-21b-a3b-serve
    (4, 16, 64, 128, jnp.bfloat16, None),   # half a lane tile of rows
    (2, 64, 64, 128, jnp.bfloat16, None),   # four tokens a tile: never built
    (8, 8, 64, 128, jnp.bfloat16, None),    # half a lane tile of rows
    (8, 64, 64, 64, jnp.bfloat16, None),    # head_dim
    (8, 64, 64, 128, jnp.float32, None),    # K and V are not one word
    (8, 64, 64, 128, jnp.float16, None),    # nor the top of a float32
    (8, 64, 64, 128, jnp.int8, None),
])
def test_tiling_from_the_shapes(kv_heads, page, pps, d, dtype, want):
    assert pd.tiling(kv_heads, page, pps, d, dtype) == want


def test_the_call_records_its_route_and_falls_back(monkeypatch):
    monkeypatch.setattr(routes, "_taken", {})
    case = _case((32, 8), [70, 130], [True, True])
    q, pages, kv_lens, table, live = case

    def never():
        raise AssertionError("eligible call fell back")

    got = pd.paged_decode_attention(
        q, pages, kv_lens, table, live, sm_scale=SM, fallback=never,
        fallback_route="xla-dense", interpret=True)
    want = _reference(q, pages, kv_lens, table, live, None)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1.6e-2)
    assert routes.taken()[pd.KERNEL] == "pallas-decode-4x8-interpret"
    # no interpreter and no TPU: the path it had, and the route says so
    assert pd.paged_decode_attention(
        q, pages, kv_lens, table, live, sm_scale=SM,
        fallback=lambda: "fell back", fallback_route="xla-dense"
    ) == "fell back"
    # nor where the layout does not hold, interpreter or not
    narrow = _case((8, 2), [70], [True])
    assert pd.paged_decode_attention(
        *narrow, sm_scale=SM, fallback=lambda: "fell back",
        fallback_route="pallas-ragged", interpret=True) == "fell back"
    assert routes.taken()[pd.KERNEL] == (
        "pallas-decode-4x8-interpret+xla-dense+pallas-ragged")
    # on a TPU backend the route names the kernel (nothing runs here)
    monkeypatch.setattr(routes, "on_tpu", lambda: True)
    assert routes.take(pd.KERNEL, eligible=True, every=True,
                       pallas="pallas-decode-4x16", xla="pallas-ragged"
                       ) == "pallas-decode-4x16"
