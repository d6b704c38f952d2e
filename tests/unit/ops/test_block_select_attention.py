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


def test_the_chunk_kernel_is_its_twin():
    r = np.random.default_rng(1)
    P, page, pp, seqs, T = 40, 16, 12, 3, 300
    pages = _pool(r, P, page)
    table = jnp.asarray(r.permutation(P - 1)[:seqs * pp].reshape(seqs, pp)
                        + 1, jnp.int32)
    cu = jnp.asarray([0, 1, 141, 191])
    first_pos = jnp.asarray([30, 50, 0])
    lay = la.tile_layout(cu, jnp.asarray([3]), first_pos, T, bsa.TILE)
    NT = lay["seq"].shape[0]
    K = bsa.mask_width(pp, page)
    q = jnp.asarray(r.normal(size=(HKV, G, NT * bsa.TILE, D)), jnp.float32
                    ).astype(jnp.bfloat16)
    pos = lay["pos0"][:, None] + jnp.arange(bsa.TILE)[None]
    live = (jnp.arange(bsa.TILE)[None] < lay["len"][:, None])
    block_ok = jnp.asarray(r.random((HKV, NT, bsa.TILE, K // page)) < 0.6)
    block_ok = block_ok.at[..., 0].set(True)
    allowed = (jnp.repeat(block_ok, page, axis=-1)
               & (jnp.arange(K)[None, None, None] <= pos[None, :, :, None])
               & live[None, :, :, None]).reshape(HKV, NT * bsa.TILE, K)
    allowed = allowed.astype(jnp.bfloat16)
    tile_last = jnp.where(lay["len"] > 0, lay["pos0"] + lay["len"] - 1, -1)
    want = bsa.chunk_twin(q, pages, allowed,
                          jnp.take(table, lay["seq"], axis=0), SCALE)
    nblk = (tile_last + 8 * page) // (8 * page)
    got = bsa._chunk(q, pages, allowed, lay["seq"], nblk.astype(jnp.int32),
                     table, sm_scale=SCALE, interpret=True)
    rows = np.asarray(live).reshape(-1)
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert diff[:, :, rows].max() < 2.0 ** -6
    assert np.abs(np.asarray(want, np.float32))[:, :, rows].max() > 0.3
    assert (np.asarray(got, np.float32)[:, :, ~rows] == 0).all()


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
