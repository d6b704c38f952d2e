"""Block selection and attention over the chosen blocks (``ops/
block_select_attention.py``): the chosen blocks are the plain reference's
(ties between neighbouring blocks included), the list form and the mask
form agree, and each kernel under the interpreter is its twin."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import minicpm_sala as ref  # noqa: E402
from deepspeed_tpu.ops import block_select_attention as bsa  # noqa: E402
from deepspeed_tpu.ops import linear_attention as la  # noqa: E402

SEL = bsa.Selection(kernel_size=8, kernel_stride=4, block_size=16,
                    init_blocks=1, window_size=32, topk=5, dense_len=64)
S, HKV, G, D = 200, 2, 2, 128
SCALE = float(1 / np.sqrt(D))


@pytest.fixture(scope="module")
def qk():
    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(size=(S, HKV * G, D)), jnp.float32)
    k = jnp.asarray(r.normal(size=(S, HKV, D)), jnp.float32)
    NB = -(-S // SEL.block_size)
    NW = NB * SEL.windows_per_block
    kp = jnp.pad(k, ((0, NW * SEL.kernel_stride + SEL.kernel_size - S),
                     (0, 0), (0, 0)))
    win = (jnp.arange(NW)[:, None] * SEL.kernel_stride
           + jnp.arange(SEL.kernel_size)[None])
    return q, jnp.mean(kp[win], axis=1).transpose(1, 0, 2), NB


def test_the_chosen_blocks_are_the_references(qk):
    """Every query past ``dense_len``: the mask and the list name the
    blocks the reference's steps (i)-(v) choose.  Neighbouring blocks tie
    whenever the window that straddles them is the best of both; the lower
    block number wins in all three."""
    q, ckeys, NB = qk
    pos = jnp.arange(S)
    mask = np.asarray(bsa.block_mask(q[None], ckeys[None], pos[None], SEL,
                                     SCALE))[0]
    blocks, count = bsa.block_list(q[None], ckeys[None], pos[None], SEL,
                                   SCALE)
    blocks, count = np.asarray(blocks)[0], np.asarray(count)[0]
    n_windows = (S - SEL.kernel_size) // SEL.kernel_stride + 1
    ties = 0
    for t in range(S):
        if t < SEL.dense_len:
            want = np.broadcast_to(np.arange(NB) <= t // 16, (HKV, NB))
        else:
            want = np.asarray(ref._chosen_blocks(
                q[t].reshape(HKV, G, D), t, ckeys[:, :n_windows], NB,
                SEL._asdict(), None))
            score = np.asarray(bsa.block_scores(
                q[None, t:t + 1], ckeys[None], pos[None, t:t + 1], SEL,
                SCALE)[0])[0, 0]
            finite = score[np.isfinite(score)]
            ties += len(finite) - len(np.unique(finite))
        assert (mask[t] == want).all(), t
        assert count[t] == want[0].sum()
        for g in range(HKV):
            assert blocks[t, g, :count[t]].tolist() == np.nonzero(
                want[g])[0].tolist()
            assert blocks[t, g, count[t] - 1] == t // SEL.block_size
    assert ties > 10                    # the case is in the test


def _pool(r, P, page):
    return jnp.asarray(r.normal(size=(P, 2 * HKV, page, D)), jnp.float32
                       ).astype(jnp.bfloat16)


# (packed rows' cu_q_lens, each sequence's first position, dense_len, the
# choice).  A page is 16 keys, a key step 128, a tile 128 rows; "random" is 60
# % of the blocks for a row at or past dense_len (and every block before it,
# as ``block_mask`` answers), "poisoned" is all zero
CHUNK_CASES = {
    # tiles wholly before dense_len read no choice: plain causal attention
    "before-dense-len-choice-poisoned": ([0, 256], [0], 10_000, "poisoned"),
    "tile-straddles-dense-len": ([0, 128], [64], 128, "random"),
    "tile-past-dense-len": ([0, 128], [200], 64, "random"),
    "last-tile-partial": ([0, 141], [50], 100, "random"),
    # one sequence of two rows among four slots: five of six tiles are empty
    "empty-tiles": ([0, 1, 3, 4, 4], [30, 140, 7, 0], 64, "random"),
    "two-sequences-two-page-tables": ([0, 1, 141, 291], [30, 50, 0], 128,
                                      "random"),
    "context-ends-inside-a-key-step": ([0, 100], [100], 150, "random"),
    "a-prompt-in-three-ticks-third": ([0, 200], [230], 128, "random"),
}


@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
def test_the_chunk_kernel_is_its_twin(case):
    cu, first_pos, dense_len, choice = CHUNK_CASES[case]
    r = np.random.default_rng(1)
    page, pp, seqs, T = 16, 28, len(first_pos), 300
    P = 1 + seqs * pp
    pages = _pool(r, P, page)
    table = jnp.asarray(r.permutation(P - 1)[:seqs * pp].reshape(seqs, pp)
                        + 1, jnp.int32)
    lay = la.tile_layout(jnp.asarray(cu), jnp.asarray([seqs]),
                         jnp.asarray(first_pos), T, bsa.TILE)
    NT = lay["seq"].shape[0]
    blocks = bsa.chunk_blocks(pp)
    q = jnp.asarray(r.normal(size=(HKV, G, NT * bsa.TILE, D)), jnp.float32
                    ).astype(jnp.bfloat16)
    pos = lay["pos0"][:, None] + jnp.arange(bsa.TILE)[None]
    live = np.asarray(jnp.arange(bsa.TILE)[None] < lay["len"][:, None])
    if choice == "poisoned":
        chosen = jnp.zeros((HKV, NT, blocks, bsa.TILE), jnp.float32)
        plain = jnp.ones_like(chosen)
    else:
        chosen = jnp.asarray(r.random((HKV, NT, blocks, bsa.TILE)) < 0.6)
        chosen = jnp.logical_or(chosen, (pos < dense_len)[None, :, None])
        # the first live row past dense_len chose nothing: it comes back zero
        far = np.argwhere(live & (np.asarray(pos) >= dense_len))[0]
        chosen = chosen.at[:, far[0], :, far[1]].set(False)
        chosen = chosen.astype(jnp.float32)
    args = (jnp.take(table, lay["seq"], axis=0), lay["pos0"], lay["len"])
    want = bsa.chunk_twin(q, pages, chosen, *args, dense_len, SCALE)
    if choice == "poisoned":
        # every block chosen everywhere: the causal mask and nothing else
        want = bsa.chunk_twin(q, pages, plain, *args, 0, SCALE)
    got = bsa._chunk(q, pages, chosen, lay["seq"], lay["pos0"], lay["len"],
                     table, dense_len=dense_len, sm_scale=SCALE,
                     interpret=True)
    rows = live.reshape(-1)
    assert rows.sum() == sum(b - a for a, b in zip(cu, cu[1:]) if b - a > 1)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want)[:, :, rows].max() < 2.0 ** -6
    assert np.abs(want)[:, :, rows].max() > 0.3
    assert (got[:, :, ~rows] == 0).all()
    if choice == "random":
        row = far[0] * bsa.TILE + far[1]
        assert (got[:, :, row] == 0).all() and (want[:, :, row] == 0).all()


def test_the_decode_kernel_reads_the_listed_pages_and_is_its_twin():
    r = np.random.default_rng(2)
    P, page, rows, L = 60, 16, 5, 24
    pages = _pool(r, P, page)
    plist = jnp.asarray(r.integers(1, P, size=(rows, HKV, L)), jnp.int32)
    count = jnp.asarray([24, 5, 0, 1, 9], jnp.int32)
    last = jnp.asarray([16, 3, 1, 7, 16], jnp.int32)
    q = jnp.asarray(r.normal(size=(rows, HKV, 8, D)), jnp.float32
                    ).astype(jnp.bfloat16)
    want = bsa.decode_twin(q, pages, plist, count, last, SCALE)
    got = bsa._decode(q, pages, plist, count, last, sm_scale=SCALE,
                      interpret=True)
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert diff.max() < 2.0 ** -6
    assert (np.asarray(got, np.float32)[2] == 0).all()      # an idle row
    # a page that is not listed changes nothing: poison every other page
    listed = np.unique(np.concatenate([
        np.asarray(plist)[s, :, :int(count[s])].reshape(-1)
        for s in range(rows)]))
    poison = np.ones((P,), bool)
    poison[listed] = False
    spoiled = jnp.where(jnp.asarray(poison)[:, None, None, None],
                        jnp.nan, pages.astype(jnp.float32)
                        ).astype(jnp.bfloat16)
    again = bsa._decode(q, spoiled, plist, count, last, sm_scale=SCALE,
                        interpret=True)
    assert (np.asarray(again, np.float32) == np.asarray(got, np.float32)
            ).all()
