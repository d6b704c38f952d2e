"""Decaying linear attention (``ops/linear_attention.py``): the chunked
scan and the one-token step, each as its Pallas kernel under the
interpreter and as its ``jax.numpy`` twin, against the recurrence token by
token: ragged batches in tiles with a carried state, a sequence that
starts at position 0 over a slot that held another's state, slots with no
row left alone, and a decode block's eight steps against eight
recurrences."""
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import linear_attention as la

H, D, SLOTS = 3, 128, 5


def _rows(n, seed, dtype=jnp.bfloat16, scale=1.0):
    r = np.random.default_rng(seed)
    return tuple(jnp.asarray(r.normal(size=(n, H, D)) * s, jnp.float32
                             ).astype(dtype)
                 for s in (0.09 * scale, scale, scale))


def _scan(kind, *args):
    q, k, v, ld, state, slot, init, length = args
    if kind == "twin":
        return la.scan_twin(q, k, v, ld, state, slot, init, length)
    return la._scan(q, k, v, la._lam_lanes(ld), state, slot, init, length,
                    interpret=True)


def test_the_head_wise_slopes_are_lightning_attentions():
    ld = np.asarray(la.head_log_decay(32))
    assert np.allclose(ld[0], -2.0 ** -0.25) and np.allclose(ld[-1],
                                                            -2.0 ** -8)
    assert (np.diff(ld) > 0).all()


def test_tile_layout_gives_each_multi_row_sequence_whole_tiles():
    cu = jnp.asarray([0, 1, 200, 201, 203, 203])
    lay = la.tile_layout(cu, jnp.asarray([4]), jnp.asarray([7, 128, 9, 0, 0]),
                         300, la.CHUNK)
    n = int(lay["n"][0])
    assert n == 3                       # 199 rows: two tiles; 2 rows: one
    assert lay["seq"][:n].tolist() == [1, 1, 3]
    assert lay["len"][:n].tolist() == [128, 71, 2]
    assert lay["init"][:n].tolist() == [la.LOAD, la.CONTINUE, la.ZERO]
    assert lay["pos0"][:n].tolist() == [128, 256, 0]
    rows = np.asarray(lay["rows"]).reshape(-1, la.CHUNK)
    assert rows[0].tolist() == list(range(1, 129))
    assert rows[1, :71].tolist() == list(range(129, 200))
    assert (rows[1, 71:] == 300).all() and rows[2, :2].tolist() == [201, 202]
    assert (np.asarray(lay["len"][n:]) == 0).all()
    assert (np.asarray(lay["seq"][n:]) == 3).all()      # blocks stay put
    # no multi-row sequence at all: tile 0 copies a state onto itself
    none = la.tile_layout(jnp.asarray([0, 1, 2, 2, 2, 2]), jnp.asarray([2]),
                          jnp.zeros((5,), jnp.int32), 300, la.CHUNK)
    assert int(none["n"][0]) == 0 and int(none["init"][0]) == la.LOAD
    assert (np.asarray(none["len"]) == 0).all()


@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_the_chunked_scan_is_the_recurrence_over_a_ragged_batch(kind):
    """Three sequences in one batch: one continues a carried state over
    three tiles, one starts at position 0 in a slot that held garbage, one
    is a single row (left to the decode step); two slots have no row."""
    T = 400
    cu = jnp.asarray([0, 1, 261, 391, 391, 391])
    first_pos = jnp.asarray([40, 300, 0, 0, 0])
    seq_slot = jnp.asarray([4, 1, 3, 0, 2])
    q, k, v = _rows(T, 0)
    ld = la.head_log_decay(H)
    state = jnp.asarray(np.random.default_rng(1).normal(
        size=(SLOTS, H, D, D)), jnp.float32)
    lay = la.tile_layout(cu, jnp.asarray([3]), first_pos, T, la.CHUNK)
    tiles = lambda a: la.to_tiles(a, lay["rows"]).transpose(1, 0, 2)
    o_t, new = _scan(kind, tiles(q), tiles(k), tiles(v), ld, state + 0,
                     jnp.take(seq_slot, lay["seq"]), lay["init"], lay["len"])
    o = la.from_tiles(o_t.transpose(1, 0, 2), lay["rows"], T
                      ).astype(jnp.float32)
    want1, s1 = la.recurrence(q[1:261], k[1:261], v[1:261], ld, state[1])
    want2, s2 = la.recurrence(q[261:391], k[261:391], v[261:391], ld,
                              jnp.zeros_like(state[3]))
    bf16 = 2.0 ** -8
    for got, want in ((o[1:261], want1), (o[261:391], want2)):
        assert float(jnp.abs(got - want).max()) <= bf16 * float(
            jnp.abs(want).max())
    assert float(jnp.abs(new[1] - s1).max()) < 1e-4
    assert float(jnp.abs(new[3] - s2).max()) < 1e-4
    for untouched in (0, 2, 4):
        assert (new[untouched] == state[untouched]).all()
    assert (o[0] == 0).all() and (o[391:] == 0).all()


@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_a_decode_blocks_eight_steps_are_eight_recurrences(kind):
    """Three live rows of four, their slots out of order, one of them
    fresh (it reads zeros whatever its slot held): eight steps in place
    against eight recurrences; the idle slots' states do not move."""
    R = 4
    ld = la.head_log_decay(H)
    state = jnp.asarray(np.random.default_rng(2).normal(
        size=(SLOTS, H, D, D)), jnp.float32)
    live = jnp.asarray([True, False, True, True])
    order, n = la.live_first(live)
    assert order.tolist() == [0, 2, 3, 1] and n.tolist() == [3]
    slot = jnp.asarray([4, 0, 1, 3])[order]         # row -> slot
    q, k, v = (a.reshape(8, R, H, D) for a in _rows(8 * R, 3))
    run = state + 0                     # the kernel donates its state
    outs = []
    for t in range(8):
        fresh = jnp.asarray([False, False, t == 0, False])[order]
        args = (q[t][order], k[t][order], v[t][order])
        if kind == "twin":
            o, run = la.decode_step_twin(*args, ld, run, slot, fresh, n)
        else:
            o, run = la._step(*args, la._lam_lanes(ld), run, slot, fresh, n,
                              interpret=True)
        outs.append(jnp.zeros_like(o).at[order].set(o))
    outs = jnp.stack(outs).astype(jnp.float32)              # [8, R, H, D]
    for row, s, zero in ((0, 4, False), (2, 1, True), (3, 3, False)):
        start = jnp.zeros_like(state[s]) if zero else state[s]
        want, s_want = la.recurrence(q[:, row], k[:, row], v[:, row], ld,
                                     start)
        assert float(jnp.abs(outs[:, row] - want).max()) <= \
            2.0 ** -8 * float(jnp.abs(want).max())
        assert float(jnp.abs(run[s] - s_want).max()) < 1e-4
    assert (outs[:, 1] == 0).all()
    for idle in (0, 2):
        assert (run[idle] == state[idle]).all()


@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_a_step_with_no_live_row_moves_nothing(kind):
    ld = la.head_log_decay(H)
    state = jnp.asarray(np.random.default_rng(4).normal(
        size=(SLOTS, H, D, D)), jnp.float32)
    q, k, v = _rows(3, 6)
    slot, fresh, n = (jnp.asarray([2, 0, 1]), jnp.zeros((3,), bool),
                      jnp.asarray([0]))
    if kind == "twin":
        o, new = la.decode_step_twin(q, k, v, ld, state + 0, slot, fresh, n)
    else:
        o, new = la._step(q, k, v, la._lam_lanes(ld), state + 0, slot, fresh,
                          n, interpret=True)
    assert (o == 0).all() and (new == state).all()


def test_the_fastest_heads_decay_underflows_and_nothing_overflows():
    """``l^128`` of head 0 of 32 is 1e-47: the table is the exponential of
    a difference, never a quotient of powers."""
    ld = la.head_log_decay(32)[:1]
    q, k, v = (a[:, :1] for a in _rows(la.CHUNK, 5))
    o, s = la._chunk_math(q[:, 0], k[:, 0], v[:, 0], ld[0],
                          jnp.ones((D, D), jnp.float32),
                          jnp.asarray(la.CHUNK))
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
