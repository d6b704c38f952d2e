"""The gated delta rule (``ops/gated_delta_rule.py``): the chunked scan and
the one-token step, each as its Pallas kernel under the interpreter and as
its ``jax.numpy`` twin, against the recurrence token by token, at the
published 96 / 192: ragged tile lengths with a carried state, a sequence
that starts at position 0 over a slot that held another's state, slots with
no row left alone, ``b`` near 0 and near 2, ``g`` of -20 and of -1e-4, and a
decode block's eight steps against eight recurrences."""
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import gated_delta_rule as gd
from deepspeed_tpu.ops import linear_attention as la

H, DK, DV, SLOTS = 2, 96, 192, 5
BF16 = 2.0 ** -8


def _rows(n, seed, dtype=jnp.bfloat16, g_range=(-3.0, -1e-4),
          b_range=(0.0, 2.0)):
    """q and k of unit length a head (q / sqrt(dk) besides), v of unit
    scale, g and b uniform over their ranges."""
    r = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(r.normal(size=(n, H, DK))) / np.sqrt(DK)
    k = unit(r.normal(size=(n, H, DK)))
    v = r.normal(size=(n, H, DV))
    g = r.uniform(*g_range, size=(n, H))
    b = r.uniform(*b_range, size=(n, H))
    cast = lambda a, t: jnp.asarray(a, jnp.float32).astype(t)
    return (cast(q, dtype), cast(k, dtype), cast(v, dtype),
            cast(g, jnp.float32), cast(b, jnp.float32))


def _state(seed):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(SLOTS, H, DK, DV)), jnp.float32)


def _scan(kind, chunk, q, k, v, g, b, state, slot, init, length):
    if kind == "twin":
        return gd.scan_twin(q, k, v, g, b, state, slot, init, length,
                            chunk=chunk)
    return gd._scan(q, k, v, gd._aux(g, b, chunk), state, slot, init, length,
                    chunk=chunk, interpret=True)


def _ragged_scan(kind, chunk, rows, state, cu, first_pos, seq_slot, seqs):
    q, k, v, g, b = rows
    T = q.shape[0]
    lay = la.tile_layout(cu, jnp.asarray([seqs]), first_pos, T, chunk)
    tiles = lambda a: la.to_tiles(a, lay["rows"]).swapaxes(0, 1)
    o_t, new = _scan(kind, chunk, tiles(q), tiles(k), tiles(v), tiles(g),
                     tiles(b), gd._heads_along_lanes(state),
                     jnp.take(seq_slot, lay["seq"]), lay["init"], lay["len"])
    o = la.from_tiles(o_t.transpose(1, 0, 2), lay["rows"], T)
    return o.astype(jnp.float32), gd._heads_first(new, H)


def _close(got, want, tol=BF16):
    assert float(jnp.abs(got - want).max()) <= tol * max(
        float(jnp.abs(want).max()), 1e-3)


@pytest.mark.parametrize("chunk", [64, 128])
@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_the_chunked_scan_is_the_recurrence_over_a_ragged_batch(kind, chunk):
    """Three sequences in one batch: one continues a carried state over
    tiles whose last is ragged, one starts at position 0 in a slot that
    held garbage, one is a single row (left to the decode step); two slots
    have no row."""
    T = 300
    cu = jnp.asarray([0, 1, 201, 291, 291, 291])
    first_pos = jnp.asarray([40, 300, 0, 0, 0])
    seq_slot = jnp.asarray([4, 1, 3, 0, 2])
    rows, state = _rows(T, 0), _state(1)
    o, new = _ragged_scan(kind, chunk, rows, state, cu, first_pos, seq_slot,
                          3)
    cut = lambda lo, hi: tuple(a[lo:hi] for a in rows)
    want1, s1 = gd.recurrence(*cut(1, 201), state[1])
    want2, s2 = gd.recurrence(*cut(201, 291), jnp.zeros_like(state[3]))
    _close(o[1:201], want1)
    _close(o[201:291], want2)
    _close(new[1], s1, 1e-4)
    _close(new[3], s2, 1e-4)
    for untouched in (0, 2, 4):
        assert (new[untouched] == state[untouched]).all()
    assert (o[0] == 0).all() and (o[291:] == 0).all()


@pytest.mark.parametrize("g_range,b_range", [
    pytest.param((-20.0, -19.0), (1.9, 2.0), id="fast-decay-b-near-2"),
    pytest.param((-1e-4, -1e-4), (1.9, 2.0), id="no-decay-b-near-2"),
    pytest.param((-1e-4, -1e-4), (0.0, 1e-3), id="no-decay-b-near-0"),
    pytest.param((-20.0, -1e-4), (0.0, 2.0), id="whole-ranges"),
])
@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_the_scan_holds_at_the_ends_of_the_gates_ranges(kind, g_range,
                                                        b_range):
    """``b`` near 2 makes the transition's eigenvalue along ``k`` nearly -a
    (the state's answer flips), ``g`` of -20 underflows a tile's decay and
    of -1e-4 keeps 200 tokens' corrections alive: nothing overflows and the
    solve is the recurrence."""
    T = 200
    rows, state = _rows(T, 7, g_range=g_range, b_range=b_range), _state(8)
    o, new = _ragged_scan(kind, gd.CHUNK, rows, state, jnp.asarray([0, T]),
                          jnp.asarray([5]), jnp.asarray([2]), 1)
    want, s = gd.recurrence(*rows, state[2])
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(new).all())
    _close(o, want)
    _close(new[2], s, 1e-4)


@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_float32_rows_take_the_same_scan(kind):
    T = 70
    rows, state = _rows(T, 9, dtype=jnp.float32), _state(10)
    o, new = _ragged_scan(kind, 64, rows, state, jnp.asarray([0, T]),
                          jnp.asarray([0]), jnp.asarray([1]), 1)
    want, s = gd.recurrence(*rows, jnp.zeros_like(state[1]))
    _close(o, want, 1e-5)
    _close(new[1], s, 1e-5)


@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_a_decode_blocks_eight_steps_are_eight_recurrences(kind):
    """Three live rows of four, their slots out of order, one of them
    fresh (it reads zeros whatever its slot held): eight steps in place
    against eight recurrences; the idle slots' states do not move."""
    R = 4
    state = _state(2)
    live = jnp.asarray([True, False, True, True])
    order, n = la.live_first(live)
    slot = jnp.asarray([4, 0, 1, 3])[order]         # row -> slot
    rows = tuple(a.reshape((8, R) + a.shape[1:]) for a in _rows(8 * R, 3))
    run = gd._heads_along_lanes(state)
    outs = []
    for t in range(8):
        fresh = jnp.asarray([False, False, t == 0, False])[order]
        args = tuple(a[t][order] for a in rows)
        step = (gd.decode_step_twin if kind == "twin" else
                lambda *a: gd._step(*a, interpret=True))
        o, run = step(*args, run, slot, fresh, n)
        outs.append(jnp.zeros_like(o).at[order].set(o))
    outs = jnp.stack(outs).astype(jnp.float32)              # [8, R, H, DV]
    run = gd._heads_first(run, H)
    for row, s, zero in ((0, 4, False), (2, 1, True), (3, 3, False)):
        start = jnp.zeros_like(state[s]) if zero else state[s]
        want, s_want = gd.recurrence(*(a[:, row] for a in rows), start)
        _close(outs[:, row], want)
        _close(run[s], s_want, 1e-4)
    assert (outs[:, 1] == 0).all()
    for idle in (0, 2):
        assert (run[idle] == state[idle]).all()


@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_a_step_with_no_live_row_moves_nothing(kind):
    state = gd._heads_along_lanes(_state(4))
    rows = _rows(3, 6)
    slot, fresh, n = (jnp.asarray([2, 0, 1]), jnp.zeros((3,), bool),
                      jnp.asarray([0]))
    if kind == "twin":
        o, new = gd.decode_step_twin(*rows, state + 0, slot, fresh, n)
    else:
        o, new = gd._step(*rows, state + 0, slot, fresh, n, interpret=True)
    assert (o == 0).all() and (new == state).all()


def test_the_state_lies_heads_along_the_lanes_in_whole_tiles():
    """``[slots, 96, 30 * 192]`` is 45 whole lane tiles; a head's columns
    are where ``_heads_first`` finds them."""
    st = jnp.arange(2 * 3 * 4 * 5, dtype=jnp.float32).reshape(2, 3, 4, 5)
    flat = gd._heads_along_lanes(st)
    assert flat.shape == (2, 4, 15)
    assert (flat[:, :, 5:10] == st[:, 1]).all()
    assert (gd._heads_first(flat, 3) == st).all()
    assert (30 * 192) % 128 == 0 and 192 % 128 != 0
    assert gd.scan_eligible(30, 96, 192, jnp.bfloat16)
    assert gd.step_heads(30, 192) == 6
    assert not gd.scan_eligible(30, 96, 192, jnp.float32)
