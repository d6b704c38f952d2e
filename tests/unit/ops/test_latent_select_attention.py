"""``ops/latent_select_attention.py`` (latent attention over the rows a
choice by token leaves) and the indexer's kernels at GLM-5's layout, ONE
index key a row of 128 lanes (``index_pack`` 1): the kernels under the
Pallas interpreter against their ``jax.numpy`` twins, and the twins against
the definition written out query by query."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import latent_select_attention as lsa
from deepspeed_tpu.ops import token_select_attention as tsa

PAGE, PP, W, RANK, HI, DI, TOPK = 16, 20, 256, 128, 4, 128, 24
P = 70
TILE = tsa.TILE
K = tsa.padded_keys(PP, PAGE)
SCALE = 0.11


def _pool(seed=0, lens=(300, 130, 7), PP=PP, P=P):
    """Sequences of ``lens`` cached tokens on shuffled pages: the page
    table, the latent pool ``[P, page, W]`` (columns past 192 zero, as the
    pool pads a row) and the index pool, a key a row."""
    rng = np.random.default_rng(seed)
    table = np.full((len(lens), PP), -1, np.int32)
    free = rng.permutation(np.arange(1, P))
    at = 0
    for s, n in enumerate(lens):
        need = -(-int(n) // PAGE)
        table[s, :need] = free[at:at + need]
        at += need
    key = jax.random.PRNGKey(seed)
    rows = jax.random.normal(key, (P, PAGE, W), jnp.bfloat16)
    rows = jnp.where(jnp.arange(W) < 192, rows, 0)
    ik = jax.random.normal(jax.random.fold_in(key, 1), (P, PAGE, DI),
                           jnp.bfloat16)
    assert tsa.index_pack(PAGE, DI) == 1
    assert ik.shape == tsa.index_leaf_shape(P, PAGE, DI)
    return jnp.asarray(table), rows, ik


def _queries(seed, NT, H):
    key = jax.random.PRNGKey(100 + seed)
    qI = jax.random.normal(key, (NT, HI * TILE, DI), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (NT, 1, HI * TILE),
                          jnp.float32) * 0.1
    q = jax.random.normal(jax.random.fold_in(key, 2), (H, NT * TILE, W),
                          jnp.bfloat16)
    return qI, w, jnp.where(jnp.arange(W) < 192, q, 0)


def _live_keys(pos0, length):
    step = tsa.key_step(PAGE)
    last = np.asarray(pos0) + np.asarray(length) - 1
    return np.where(np.asarray(length) > 0, (last // step + 1) * step, 0)


# name: (cached tokens a sequence, tiles as (seq, pos0, len)); ``zero_q``:
# index queries of zeros, so every visible score is 0 and every threshold
# is shared
CASES = {
    # a chunk that starts in the middle of a page, rows on both sides of
    # index_topk in one tile (positions 13 .. 140, topk 24)
    "chunk_starts_mid_page_and_crosses_topk": dict(
        lens=(300, 141, 7), tiles=[(1, 13, 128), (0, 0, 0)]),
    # rows at and around index_topk: t + 1 = 23, 24, 25
    "rows_around_topk": dict(
        lens=(300, 130, 26), tiles=[(2, 22, 3), (2, 0, 22)]),
    "two_sequences_and_a_dead_tile": dict(
        lens=(300, 130, 7), tiles=[(0, 100, 128), (0, 228, 72), (1, 2, 128),
                                   (1, 0, 0), (2, 0, 7)]),
    "threshold_shared_by_every_key": dict(
        lens=(300, 130, 7), zero_q=True,
        tiles=[(0, 40, 3), (1, 2, 128), (1, 0, 0)]),
}


def _case(name, H, seed=6):
    c = CASES[name]
    table, rows, ik = _pool(seed, c["lens"])
    seq, pos0, length = (jnp.asarray(a, jnp.int32) for a in zip(*c["tiles"]))
    qI, w, q = _queries(seed, len(c["tiles"]), H)
    if c.get("zero_q"):
        qI = jnp.zeros_like(qI)
    return table, rows, ik, (seq, pos0, length), qI, w, q


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_indexers_kernels_at_one_key_a_row_are_their_twins(case):
    """``index_select`` at ``index_pack`` 1 (GLM-5's 128-wide index key: a
    token a row of the index pool) under the interpreter against the three
    twins in their order: the same statistics and, wherever the kernel
    writes, the same bias bit for bit; ``min(t + 1, topk)`` keys a row."""
    table, _, ik, where, qI, w, _ = _case(case, 3)
    seq, pos0, length = where
    scores = tsa.index_scores_twin(qI, w, ik, table, *where)
    stats_t, bias_t = tsa.select_twin(scores, TOPK)
    bias_t = tsa.resolve_ties(scores, stats_t, bias_t, TOPK)
    stats, bias = tsa.index_select(qI, w, ik, table, *where, topk=TOPK,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(stats[:, :2]),
                                  np.asarray(stats_t[:, :2]))
    live = _live_keys(pos0, length)
    for n, upto in enumerate(live):
        np.testing.assert_array_equal(
            np.asarray(bias[n, :upto], np.float32),
            np.asarray(bias_t[n, :upto], np.float32))
    c = np.arange(K)[None, :, None]
    chosen = np.logical_and(c < live[:, None, None],
                            np.asarray(bias, np.float32) == 0)
    t = np.asarray(pos0)[:, None] + np.arange(TILE)[None]
    on = np.arange(TILE)[None] < np.asarray(length)[:, None]
    np.testing.assert_array_equal(
        chosen.sum(axis=1), np.where(on, np.minimum(t + 1, TOPK), 0))
    if case == "threshold_shared_by_every_key":
        # the lower positions win
        assert chosen[1, :, 127].nonzero()[0].tolist() == list(range(TOPK))
    if case == "rows_around_topk":
        assert chosen[0].sum(axis=0)[:3].tolist() == [23, 24, 24]


def test_a_decode_rows_scores_at_one_key_a_row_are_the_twins():
    table, _, ik = _pool(2)
    S = table.shape[0]
    key = jax.random.PRNGKey(9)
    qI = jax.random.normal(key, (S, 8, DI), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (S, 8), jnp.float32)
    pos = jnp.asarray([299, 77, 6], jnp.int32)
    on = jnp.asarray([True, True, False])
    got = tsa.row_index_scores(qI, w, ik, table, pos, on, interpret=True)
    want = jnp.where(on[:, None], tsa.row_scores(
        qI, w, tsa.sequence_keys(ik, table, DI), pos), -jnp.inf)
    assert got.shape == (S, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    assert np.isneginf(np.asarray(got)[0, 300:]).all()
    assert np.isneginf(np.asarray(got)[2]).all()


def _definition(q, rows_seq, chosen, rank=RANK):
    """One query's heads ``q [H, W]`` over the chosen rows of its sequence
    ``rows_seq [n, W]``: softmax over the chosen, the sum of their first
    ``rank`` columns."""
    keys = np.asarray(rows_seq, np.float32)[chosen]
    s = np.asarray(q, np.float32) @ keys.T * SCALE
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return p @ keys[:, :rank]


@pytest.mark.parametrize("heads", [3, 32])
@pytest.mark.parametrize("case", sorted(CASES))
def test_chunk_kernel_is_its_twin_and_the_definition(case, heads):
    """``chunk_attention`` under the interpreter, every head a grid step (3
    heads) and sixteen a step (32), against the twin under the same bias
    and, for single rows, against the definition over exactly the keys the
    bias opens."""
    table, rows, ik, where, qI, w, q = _case(case, heads)
    seq, pos0, length = where
    assert lsa.heads_a_step(heads) == (16 if heads == 32 else 3)
    scores = tsa.index_scores_twin(qI, w, ik, table, *where)
    stats, bias = tsa.select_twin(scores, TOPK)
    bias = tsa.resolve_ties(scores, stats, bias, TOPK)
    twin = lsa.chunk_twin(q, rows, bias, jnp.take(table, seq, axis=0),
                          rank=RANK, sm_scale=SCALE)
    # the kernel reads the bias of a tile's live key steps alone
    c = np.arange(K)[None, :, None]
    poisoned = jnp.where(c < _live_keys(pos0, length)[:, None, None], bias,
                         jnp.nan)
    got = lsa.chunk_attention(q, rows, poisoned, *where, table, rank=RANK,
                              sm_scale=SCALE, interpret=True)
    assert got.shape == (heads, q.shape[1], RANK) and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(twin, np.float32), rtol=2e-2,
                               atol=2e-2)
    # rows past a tile's length, and a tile of no row, come back zero
    on = (np.arange(TILE)[None] < np.asarray(length)[:, None]).reshape(-1)
    assert not np.asarray(got, np.float32)[:, ~on].any()
    # the definition, for each tile's first and last live row
    flat = np.asarray(rows).reshape(-1, W)
    for n, (s_, p0, ln) in enumerate(zip(*map(np.asarray, where))):
        for i in {0, int(ln) - 1} if ln else ():
            t = int(p0) + i
            slots = np.asarray(table)[s_, np.arange(t + 1) // PAGE] * PAGE \
                + np.arange(t + 1) % PAGE
            chosen = (np.asarray(bias, np.float32)[n, :t + 1, i] == 0)
            assert chosen.sum() == min(t + 1, TOPK)
            want = _definition(np.asarray(q)[:, n * TILE + i],
                               flat[slots], chosen.nonzero()[0])
            np.testing.assert_allclose(
                np.asarray(got, np.float32)[:, n * TILE + i], want,
                rtol=3e-2, atol=3e-2)


def test_a_decode_row_gathers_its_chosen_rows_and_reads_no_other():
    table, rows, _ = _pool(4)
    S, H, k = table.shape[0], 5, TOPK
    q = jax.random.normal(jax.random.PRNGKey(5), (S, H, W), jnp.bfloat16)
    rng = np.random.default_rng(0)
    tokens = np.zeros((S, k), np.int32)
    taken = np.zeros((S, k), bool)
    for s, n in enumerate((300, 130, 7)):
        m = min(n, k)
        tokens[s, :m] = rng.choice(n, size=m, replace=False)
        taken[s, :m] = True
    # every row the choice did not name is poison: a read of one would show
    named = np.zeros(rows.shape[:2], bool).reshape(-1)
    for s in range(S):
        t = tokens[s][taken[s]]
        named[np.asarray(table)[s, t // PAGE] * PAGE + t % PAGE] = True
    poisoned = jnp.where(named.reshape(rows.shape[:2])[..., None], rows,
                         jnp.nan)
    got = lsa.row_attention(q, poisoned, table, jnp.asarray(tokens),
                            jnp.asarray(taken), rank=RANK, sm_scale=SCALE)
    assert got.shape == (S, H, RANK) and np.isfinite(
        np.asarray(got, np.float32)).all()
    flat = np.asarray(rows).reshape(-1, W)
    for s in range(S):
        t = tokens[s][taken[s]]
        slots = np.asarray(table)[s, t // PAGE] * PAGE + t % PAGE
        want = _definition(np.asarray(q)[s], flat[slots],
                           np.arange(len(slots)))
        np.testing.assert_allclose(np.asarray(got, np.float32)[s], want,
                                   rtol=3e-2, atol=3e-2)
    assert lsa.decode_route(2048) == "xla-latent-token-gather-2048"
