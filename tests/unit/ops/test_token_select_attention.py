"""``ops/token_select_attention.py``: the kernels (under the Pallas
interpreter) against their ``jax.numpy`` twins, and the twins against the
definition written out query by query."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import token_select_attention as tsa

PAGE, PP, D, HKV, G, HI, DI, TOPK = 16, 20, 128, 2, 2, 4, 64, 24
S, P = 3, 70
TILE = tsa.TILE
K = tsa.padded_keys(PP, PAGE)


def pack_index_keys(keys):
    """``keys [P, page, dI]`` (a page's tokens in order) as the pool holds
    them, written out from the rule and not with the program's code:
    ``[P, page // pack, pack * dI]``, token ``j`` in row ``j % rows`` at
    lanes ``(j // rows) * dI`` on."""
    P_, page, dI = keys.shape
    _, rows, width = tsa.index_leaf_shape(P_, page, dI)
    out = np.zeros((P_, rows, width), np.float32)
    for j in range(page):
        out[:, j % rows, (j // rows) * dI:][:, :dI] = np.asarray(
            keys[:, j], np.float32)
    return jnp.asarray(out, keys.dtype)


def _pool(seed=0, lens=(300, 130, 7), PP=PP, P=P):
    """Three sequences of 300 / 130 / 7 cached tokens on shuffled pages (or
    those of ``lens``, of ``PP`` pages at most, in a pool of ``P``)."""
    rng = np.random.default_rng(seed)
    lens = np.asarray(lens)
    table = np.full((len(lens), PP), -1, np.int32)
    free = rng.permutation(np.arange(1, P))
    at = 0
    for s, n in enumerate(lens):
        need = -(-int(n) // PAGE)
        table[s, :need] = free[at:at + need]
        at += need
    key = jax.random.PRNGKey(seed)
    kv = jax.random.normal(key, (P, PAGE, 2 * HKV * D), jnp.bfloat16)
    # the index keys as the pool holds them: two a row of 128 lanes
    ik = pack_index_keys(jax.random.normal(
        jax.random.fold_in(key, 1), (P, PAGE, DI), jnp.bfloat16))
    assert ik.shape == tsa.index_leaf_shape(P, PAGE, DI) == (P, PAGE // 2, 128)
    return lens, jnp.asarray(table), kv, ik


def _tiles(lens):
    """Sequence 0's last 200 rows in two tiles, sequence 1's 130 rows in
    two, and an empty tile behind them."""
    seq = np.asarray([0, 0, 1, 1, 1], np.int32)
    pos0 = np.asarray([100, 228, 0, 128, 0], np.int32)
    length = np.asarray([128, 72, 128, 2, 0], np.int32)
    return jnp.asarray(seq), jnp.asarray(pos0), jnp.asarray(length)


def _queries(seed, NT):
    key = jax.random.PRNGKey(100 + seed)
    qI = jax.random.normal(key, (NT, HI * TILE, DI), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (NT, 1, HI * TILE),
                          jnp.float32) * 0.1
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (HKV, G, NT * TILE, D), jnp.bfloat16)
    return qI, w, q


def test_sortable_keeps_the_floats_order():
    x = jnp.asarray([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, np.inf],
                    jnp.float32)
    k = np.asarray(tsa.sortable(x))
    assert k[3] == k[4] == 0
    assert np.all(np.diff(k[[0, 1, 2, 3, 5, 6, 7]]) > 0)
    assert k.min() > np.iinfo(np.int32).min


@pytest.mark.parametrize("page,dI", [(16, 64), (64, 64), (16, 16), (16, 128),
                                     (16, 48)])
def test_the_index_pool_packs_whole_rows_and_is_written_where_it_is_read(
        page, dI):
    """A row of the index pool is as many keys as fill 128 lanes (one where
    ``dI`` does not divide them), a lane block a page's consecutive tokens;
    a key written at a token's slot is the key read at its position."""
    P_ = 5
    pack = {64: 2, 16: 8, 128: 1, 48: 1}[dI]
    assert tsa.index_pack(page, dI) == pack
    assert tsa.index_leaf_shape(P_, page, dI) == (P_, page // pack, pack * dI)
    raw = jax.random.normal(jax.random.PRNGKey(3), (P_, page, dI),
                            jnp.bfloat16)
    packed = pack_index_keys(raw)
    assert packed.shape == tsa.index_leaf_shape(P_, page, dI)
    rows = page // pack
    for j in (0, 1, rows - 1, rows % page, page - 1):
        np.testing.assert_array_equal(
            np.asarray(packed[2, j % rows, (j // rows) * dI:][:dI]),
            np.asarray(raw[2, j]))
    np.testing.assert_array_equal(
        np.asarray(tsa.unpack_index_keys(packed, dI)), np.asarray(raw))
    # written as ticks write: a call a tick, in it runs of a sequence's
    # consecutive tokens (page after page of its table), the runs in any
    # order; a slot past the pool is dropped
    run = lambda a, b: np.arange(a, b)                       # noqa: E731
    ticks = [[run(4 * page, 4 * page + page // 2)],
             [run(4 * page + page // 2, 5 * page), run(0, 5)],
             [run(5, 2 * page + 3), run(3 * page, 4 * page)],
             [[P_ * page], run(2 * page + 3, 3 * page)]]
    keys = jnp.concatenate([raw.reshape(-1, dI), jnp.ones((1, dI), raw.dtype)])
    written = jnp.zeros_like(packed)
    for tick in ticks:
        part = np.concatenate(tick)
        written = tsa.write_index_keys(written, jnp.asarray(part, jnp.int32),
                                       keys[part])
    np.testing.assert_array_equal(np.asarray(written), np.asarray(packed))
    # one token a call, as a decode tick writes a sequence's
    one = jnp.zeros_like(packed)
    for d in range(page, 2 * page):
        one = tsa.write_index_keys(one, jnp.asarray([d, 0], jnp.int32),
                                   keys[jnp.asarray([d, 0])])
    np.testing.assert_array_equal(np.asarray(one[1]), np.asarray(packed[1]))
    table = jnp.asarray([[3, 1, -1], [4, -1, -1]], jnp.int32)
    got = tsa.sequence_keys(packed, table, dI)
    assert got.shape == (2, tsa.padded_keys(3, page), dI)
    np.testing.assert_array_equal(np.asarray(got[0, :2 * page]),
                                  np.asarray(raw[jnp.asarray([3, 1])]
                                             ).reshape(-1, dI))


def _twins(qI, w, ik, table, seq, pos0, length, topk=TOPK):
    """The definition the fused kernel is held to: the three twins in
    their order -> ``(scores, stats, bias)``."""
    scores = tsa.index_scores_twin(qI, w, ik, table, seq, pos0, length)
    stats, bias = tsa.select_twin(scores, topk)
    return scores, stats, tsa.resolve_ties(scores, stats, bias, topk)


def _live_keys(pos0, length, page=PAGE):
    """Keys of each tile the fused kernel writes a bias for: its key steps
    up to its last row's own key; none in a tile of no row."""
    step = tsa.key_step(page)
    last = np.asarray(pos0) + np.asarray(length) - 1
    return np.where(np.asarray(length) > 0, (last // step + 1) * step, 0)


def _poisoned(bias, pos0, length):
    """``bias`` with NaN wherever the fused kernel writes nothing."""
    c = np.arange(bias.shape[1])[None, :, None]
    return jnp.where(c < _live_keys(pos0, length)[:, None, None], bias,
                     jnp.nan)


def _same_where_written(bias, bias_t, pos0, length):
    for n, live in enumerate(_live_keys(pos0, length)):
        np.testing.assert_array_equal(
            np.asarray(bias[n, :live], np.float32),
            np.asarray(bias_t[n, :live], np.float32))


_back = lambda a: np.asarray(jax.lax.bitcast_convert_type(    # noqa: E731
    jnp.where(a < 0, a ^ 0x7FFFFFFF, a), jnp.float32))


def test_index_scores_kernel_is_its_twin_and_the_definition():
    """The one kernel's scores, read back through what it leaves: a row's
    threshold is its ``TOPK``-th largest score and its bias opens the keys
    that score at or above it."""
    lens, table, _, ik = _pool()
    seq, pos0, length = _tiles(lens)
    qI, w, _ = _queries(0, 5)
    twin, stats_t, bias_t = _twins(qI, w, ik, table, seq, pos0, length)
    stats, bias = tsa.index_select(qI, w, ik, table, seq, pos0, length,
                                   topk=TOPK, interpret=True)
    assert bias.shape == (5, K, TILE) and bias.dtype == jnp.bfloat16
    assert stats.shape == (5, 8, TILE) and stats.dtype == jnp.int32
    # the MXU's accumulation order is not the einsum's: compare as floats
    rows = np.arange(TILE)[None] < np.asarray(length)[:, None]
    np.testing.assert_allclose(_back(stats[:, 0])[rows],
                               _back(stats_t[:, 0])[rows], rtol=1e-5,
                               atol=1e-5)
    _same_where_written(bias, bias_t, pos0, length)
    # the definition, for one row: tile 1, row 5 is position 233 of seq 0
    keys = np.asarray(tsa.sequence_keys(ik, table, DI), np.float32)[0]
    qr = np.asarray(qI, np.float32)[1].reshape(HI, TILE, DI)[:, 5]
    wr = np.asarray(w)[1, 0].reshape(HI, TILE)[:, 5]
    want = (np.maximum(qr @ keys.T, 0) * wr[:, None]).sum(0)
    np.testing.assert_allclose(_back(twin)[1, :234, 5], want[:234],
                               rtol=1e-4, atol=1e-4)
    best = np.sort(want[:234])[::-1]
    np.testing.assert_allclose(_back(stats[1, 0])[5], best[TOPK - 1],
                               rtol=1e-4, atol=1e-4)
    assert best[TOPK - 1] - best[TOPK] > 1e-3     # no near tie to blur it
    np.testing.assert_array_equal(
        (np.asarray(bias, np.float32)[1, :, 5] == 0).nonzero()[0],
        np.sort(np.argsort(-want[:234], kind="stable")[:TOPK]))
    # a key after the row's own, a row past the tile's length, a tile of no
    # row: never chosen (the last writes no bias at all)
    chosen = np.asarray(_poisoned(bias, pos0, length), np.float32) == 0
    assert not chosen[1, 234:, 5].any() and not chosen[1, :, 72:].any()
    assert not chosen[4].any()
    np.testing.assert_array_equal(np.asarray(stats[4, :2]),
                                  np.asarray(stats_t[4, :2]))


def test_select_kernel_finds_the_exact_threshold():
    lens, table, _, ik = _pool(1)
    seq, pos0, length = _tiles(lens)
    qI, w, _ = _queries(1, 5)
    scores, stats_t, bias_t = _twins(qI, w, ik, table, seq, pos0, length)
    stats, bias = tsa.index_select(qI, w, ik, table, seq, pos0, length,
                                   topk=TOPK, interpret=True)
    np.testing.assert_array_equal(np.asarray(stats[:, :2]),
                                  np.asarray(stats_t[:, :2]))
    # with four index heads a sixteenth of the scores are exactly 0 (every
    # relu shut), so some thresholds are shared: the lower positions win,
    # in the kernel as in the twin's second half
    assert (np.asarray(stats)[:, 1] > TOPK).any()
    assert (np.asarray(tsa.select_twin(scores, TOPK)[1], np.float32)
            == 0).sum() > (np.asarray(bias_t, np.float32) == 0).sum()
    _same_where_written(bias, bias_t, pos0, length)
    chosen = np.asarray(_poisoned(bias, pos0, length), np.float32) == 0
    # a row past topk chooses exactly topk keys, an earlier one all it sees
    count = chosen.sum(axis=1)
    t = np.asarray(pos0)[:, None] + np.arange(TILE)[None]
    live = np.arange(TILE)[None] < np.asarray(length)[:, None]
    np.testing.assert_array_equal(
        count, np.where(live, np.minimum(t + 1, TOPK), 0))


# name: (cached tokens a sequence, pages a sequence, tiles as (seq, pos0,
# len)); ``zero_q``: index queries of zeros, so every visible score is 0
FUSED_CASES = {
    # the last row's own key lies in key step 0 (a step is 128 keys here)
    "last_row_in_key_step_0": dict(
        lens=(300, 130, 7), PP=PP, tiles=[(1, 0, 100), (2, 0, 7), (2, 0, 0)]),
    # a chunk of 1,020 rows: the last 520 of one sequence, the first 500 of
    # the next, in nine live tiles of twelve
    "chunk_straddles_two_sequences": dict(
        lens=(640, 500), PP=40,
        tiles=[(0, 120 + 128 * i, 128) for i in range(4)] + [(0, 632, 8)]
        + [(1, 128 * i, 128) for i in range(3)] + [(1, 384, 116)]
        + [(1, 0, 0)] * 3),
    "no_dead_tile": dict(
        lens=(300, 130, 7), PP=PP, tiles=[(0, 100, 128), (0, 228, 72),
                                          (1, 2, 128)]),
    "every_tile_dead_but_one": dict(
        lens=(300, 130, 7), PP=PP, tiles=[(0, 172, 128)] + [(0, 0, 0)] * 4),
    # every visible score equal: the first topk positions win
    "threshold_shared_by_every_key": dict(
        lens=(300, 130, 7), PP=PP, zero_q=True,
        tiles=[(0, 40, 3), (1, 2, 128), (1, 0, 0)]),
    # t + 1 <= topk: every visible key is chosen
    "every_visible_key_chosen": dict(
        lens=(300, 130, 7), PP=PP, tiles=[(1, 0, TOPK), (2, 0, 7)]),
}


@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_fused_kernel_is_the_three_twins(case):
    """``index_select`` under the interpreter against ``index_scores_twin``,
    ``select_twin`` and ``resolve_ties`` in that order: the same statistics
    and, wherever the kernel writes, the same bias bit for bit."""
    c = FUSED_CASES[case]
    pool = 1 + len(c["lens"]) * c["PP"]
    lens, table, _, ik = _pool(6, c["lens"], c["PP"], pool)
    seq, pos0, length = (jnp.asarray(a, jnp.int32) for a in zip(*c["tiles"]))
    NT = len(c["tiles"])
    qI, w, _ = _queries(6, NT)
    if c.get("zero_q"):
        qI = jnp.zeros_like(qI)
    scores, stats_t, bias_t = _twins(qI, w, ik, table, seq, pos0, length)
    stats, bias = tsa.index_select(qI, w, ik, table, seq, pos0, length,
                                   topk=TOPK, interpret=True)
    assert bias.shape == (NT, tsa.padded_keys(c["PP"], PAGE), TILE)
    np.testing.assert_array_equal(np.asarray(stats[:, :2]),
                                  np.asarray(stats_t[:, :2]))
    _same_where_written(bias, bias_t, pos0, length)
    # and what it does not write, no row of the twin's bias opens
    c_ = np.arange(bias.shape[1])[None, :, None]
    assert not np.logical_and(
        c_ >= _live_keys(pos0, length)[:, None, None],
        np.asarray(bias_t, np.float32) == 0).any()
    chosen = np.asarray(_poisoned(bias, pos0, length), np.float32) == 0
    t = np.asarray(pos0)[:, None] + np.arange(TILE)[None]
    live = np.arange(TILE)[None] < np.asarray(length)[:, None]
    np.testing.assert_array_equal(
        chosen.sum(axis=1), np.where(live, np.minimum(t + 1, TOPK), 0))
    if case == "last_row_in_key_step_0":
        assert (_live_keys(pos0, length) == [128, 128, 0]).all()
    if case == "chunk_straddles_two_sequences":
        assert (np.asarray(length) > 0).sum() == 9
        assert int(np.asarray(length).sum()) == 1020
    if case == "threshold_shared_by_every_key":
        assert np.asarray(stats)[0, 1, :3].tolist() == [41, 42, 43]
        for n, i in ((0, 0), (0, 2), (1, 127)):
            assert chosen[n, :, i].nonzero()[0].tolist() == list(range(TOPK))
    if case == "every_visible_key_chosen":
        for i in (0, 5, TOPK - 1):
            assert chosen[0, :, i].nonzero()[0].tolist() == list(range(i + 1))


def test_ties_go_to_the_lower_position():
    # every visible score equal: the first topk positions win
    lens, table, _, ik = _pool()
    seq = jnp.asarray([0], jnp.int32)
    pos0 = jnp.asarray([40], jnp.int32)
    length = jnp.asarray([3], jnp.int32)
    seen = tsa._visible(K, pos0, length)
    scores = jnp.where(seen, tsa.sortable(jnp.float32(1.5)), tsa._INT_MIN)
    qI, w, _ = _queries(0, 1)
    # index queries of zeros score every key 0: as equal as 1.5 is
    for fn in (lambda: tsa.select_twin(scores, TOPK),
               lambda: tsa.index_select(jnp.zeros_like(qI), w, ik, table, seq,
                                        pos0, length, topk=TOPK,
                                        interpret=True)):
        stats, bias = fn()
        assert np.asarray(stats)[0, 1, :3].tolist() == [41, 42, 43]
        if (np.asarray(bias, np.float32) == 0).sum() > 3 * TOPK:
            bias = tsa.resolve_ties(scores, stats, bias, TOPK)  # the twin's
        fixed = np.asarray(_poisoned(bias, pos0, length), np.float32) == 0
        for i in range(3):
            assert fixed[0, :, i].nonzero()[0].tolist() == list(range(TOPK))
        assert not fixed[0, :, 3:].any()
    # and token_mask agrees, on plain floats
    m = tsa.token_mask(jnp.ones((1, 50)), jnp.arange(50)[None] <= 42, TOPK)
    assert np.asarray(m)[0].nonzero()[0].tolist() == list(range(TOPK))


def test_chunk_kernel_reads_no_bias_the_fused_kernel_did_not_write():
    """The poison test: NaN in every key step past a tile's last row's and
    in the whole of a tile of no row, which is where ``index_select``
    writes nothing; the chunk kernel answers as on the clean bias."""
    lens, table, kv, ik = _pool(7)
    seq, pos0, length = _tiles(lens)
    qI, w, q = _queries(7, 5)
    _, _, clean = _twins(qI, w, ik, table, seq, pos0, length)
    _, bias = tsa.index_select(qI, w, ik, table, seq, pos0, length,
                               topk=TOPK, interpret=True)
    poisoned = _poisoned(bias, pos0, length)
    written = np.isfinite(np.asarray(poisoned, np.float32))
    assert not written[4].any() and not written[2, 128:].any()
    assert written[1, :384].all()
    sm = float(1 / np.sqrt(D))
    got = tsa.chunk_attention(q, kv, poisoned, seq, pos0, length, table,
                              sm_scale=sm, interpret=True)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(tsa.chunk_attention(q, kv, clean, seq, pos0, length, table,
                                       sm_scale=sm, interpret=True),
                   np.float32))
    twin = tsa.chunk_twin(q, kv, clean, jnp.take(table, seq, axis=0), sm)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(twin, np.float32), atol=2e-2)


@pytest.mark.parametrize("seed", [2, 3])
def test_chunk_kernel_is_its_twin_and_masked_attention(seed):
    lens, table, kv, ik = _pool(seed)
    seq, pos0, length = _tiles(lens)
    qI, w, q = _queries(seed, 5)
    scores = tsa.index_scores_twin(qI, w, ik, table, seq, pos0, length)
    stats, bias = tsa.select_twin(scores, TOPK)
    bias = tsa.resolve_ties(scores, stats, bias, TOPK)
    sm = float(1 / np.sqrt(D))
    twin = tsa.chunk_twin(q, kv, bias, jnp.take(table, seq, axis=0), sm)
    got = tsa.chunk_attention(q, kv, bias, seq, pos0, length, table,
                              sm_scale=sm, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(twin, np.float32), atol=2e-2)
    # the definition for one row: tile 3, row 1 = position 129 of seq 1
    n, i = 3, 1
    allowed = np.asarray(bias, np.float32)[n, :, i] == 0
    assert allowed.sum() == TOPK
    pages = np.asarray(table)[1]
    rows = np.concatenate([np.asarray(kv, np.float32)[p] for p in
                           pages[:-(-130 // PAGE)]])[:130]
    rows = rows.reshape(130, 2, HKV, D)
    qr = np.asarray(q, np.float32)[:, :, n * TILE + i]       # [Hkv, G, D]
    for h in range(HKV):
        s = qr[h] @ rows[:, 0, h].T * sm
        s = np.where(allowed[:130][None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[:, 1, h]
        np.testing.assert_allclose(
            np.asarray(twin, np.float32)[h, :, n * TILE + i], want,
            atol=2e-2)
    # rows past a tile's length and the empty tile come back zero
    out = np.asarray(got, np.float32).reshape(HKV, G, 5, TILE, D)
    assert not out[:, :, 1, 72:].any() and not out[:, :, 4].any()


def test_one_row_path_chooses_as_the_mask_does_and_reads_no_other_token():
    lens, table, kv, ik = _pool(4)
    key = jax.random.PRNGKey(9)
    qI = jax.random.normal(key, (S, HI, DI), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (S, HI)) * 0.1
    q = jax.random.normal(jax.random.fold_in(key, 2), (S, HKV, G, D),
                          jnp.bfloat16)
    pos = jnp.asarray(lens - 1, jnp.int32)
    seq_keys = tsa.sequence_keys(ik, table, DI)
    scores = tsa.row_scores(qI, w, seq_keys, pos)
    tokens, taken = tsa.row_choice(scores, TOPK)
    mask = np.asarray(tsa.token_mask(scores, jnp.isfinite(scores), TOPK))
    for s in range(S):
        mine = np.sort(np.asarray(tokens)[s][np.asarray(taken)[s]])
        np.testing.assert_array_equal(mine, mask[s].nonzero()[0])
        assert len(mine) == min(int(lens[s]), TOPK)
    sm = float(1 / np.sqrt(D))
    y = tsa.row_attention(q, kv, table, tokens, taken, sm_scale=sm)
    # poison every token that was not chosen: the answer does not move
    chosen = np.zeros((P * PAGE,), bool)
    for s in range(S):
        t = np.asarray(tokens)[s][np.asarray(taken)[s]]
        chosen[np.asarray(table)[s][t // PAGE] * PAGE + t % PAGE] = True
    poisoned = jnp.where(jnp.asarray(chosen)[:, None],
                         kv.reshape(P * PAGE, -1), jnp.nan).reshape(kv.shape)
    y2 = tsa.row_attention(q, poisoned, table, tokens, taken, sm_scale=sm)
    assert np.isfinite(np.asarray(y2, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y2, np.float32))
    # against masked attention over the whole sequence, row 0
    flat = np.asarray(kv, np.float32).reshape(P * PAGE, 2, HKV, D)
    t = np.arange(int(lens[0]))
    rows = flat[np.asarray(table)[0][t // PAGE] * PAGE + t % PAGE]
    for h in range(HKV):
        sc = np.asarray(q, np.float32)[0, h] @ rows[:, 0, h].T * sm
        sc = np.where(mask[0][:len(t)][None], sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[:, 1, h]
        np.testing.assert_allclose(np.asarray(y, np.float32)[0, h], want,
                                   atol=2e-2)


def test_row_index_kernel_is_its_twin():
    lens, table, _, ik = _pool(5)
    key = jax.random.PRNGKey(11)
    qI = jax.random.normal(key, (S, 8, DI), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (S, 8)) * 0.1
    pos = jnp.asarray(lens - 1, jnp.int32)
    on = jnp.asarray([True, False, True])
    twin = tsa.row_scores(qI, w, tsa.sequence_keys(ik, table, DI), pos)
    got = tsa.row_index_scores(qI, w, ik, table, pos, on, interpret=True)
    assert got.shape == (S, K)
    assert np.isneginf(np.asarray(got)[1]).all()
    for s in (0, 2):
        seen = np.isfinite(np.asarray(twin)[s])
        assert seen.sum() == lens[s]
        np.testing.assert_array_equal(np.isfinite(np.asarray(got)[s]), seen)
        np.testing.assert_allclose(np.asarray(got)[s][seen],
                                   np.asarray(twin)[s][seen], rtol=1e-5,
                                   atol=1e-5)
