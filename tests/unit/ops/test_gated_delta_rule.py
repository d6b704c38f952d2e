"""The gated delta rule (``ops/gated_delta_rule.py``): the chunked scan and
the one-token step, each as its Pallas kernel under the interpreter and as
its ``jax.numpy`` twin, against the recurrence token by token, at the
published 96 / 192: ragged tile lengths with a carried state, a sequence
that starts at position 0 over a slot that held another's state, slots with
no row left alone, ``b`` near 0 and near 2, ``g`` of -20 and of -1e-4, and a
decode block's eight steps against eight recurrences.  The scan takes its
rows as the projections leave them (``[rows, H * d]``, a head a lane block):
the parent's heads-first layout (``to_tiles(..).swapaxes``) is kept HERE as
the reference its results are held to, to the bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import gated_delta_rule as gd
from deepspeed_tpu.ops import linear_attention as la

H, DK, DV, SLOTS = 2, 96, 192, 5
BF16 = 2.0 ** -8


def _rows(n, seed, dtype=jnp.bfloat16, g_range=(-3.0, -1e-4),
          b_range=(0.0, 2.0), heads=H, dk=DK):
    """q and k of unit length a head (q / sqrt(dk) besides), v of unit
    scale, g and b uniform over their ranges."""
    r = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(r.normal(size=(n, heads, dk))) / np.sqrt(dk)
    k = unit(r.normal(size=(n, heads, dk)))
    v = r.normal(size=(n, heads, DV))
    g = r.uniform(*g_range, size=(n, heads))
    b = r.uniform(*b_range, size=(n, heads))
    cast = lambda a, t: jnp.asarray(a, jnp.float32).astype(t)
    return (cast(q, dtype), cast(k, dtype), cast(v, dtype),
            cast(g, jnp.float32), cast(b, jnp.float32))


def _state(seed, heads=H, dk=DK):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(SLOTS, heads, dk, DV)), jnp.float32)


def _scan(kind, chunk, q, k, v, g, b, state, slot, init, length):
    if kind == "twin":
        return gd.scan_twin(q, k, v, g, b, state, slot, init, length,
                            chunk=chunk)
    return gd._scan(q, k, v, gd._aux(g, b, chunk), state, slot, init, length,
                    chunk=chunk, interpret=True)


def _ragged_scan(kind, chunk, rows, state, cu, first_pos, seq_slot, seqs):
    q, k, v, g, b = rows
    T, heads = g.shape
    lay = la.tile_layout(cu, jnp.asarray([seqs]), first_pos, T, chunk)
    # as ``paged/delta_state.py`` lays them out: what a tile holds past its
    # live rows is the batch's last row, and the scan zeroes it
    tiles = lambda a: la.rows_to_tiles(a.reshape(T, -1), lay["rows"])
    o_t, new = _scan(kind, chunk, tiles(q), tiles(k), tiles(v),
                     la.to_tiles(g, lay["rows"]), la.to_tiles(b, lay["rows"]),
                     gd._heads_along_lanes(state),
                     jnp.take(seq_slot, lay["seq"]), lay["init"], lay["len"])
    o = la.tiles_to_rows(o_t, lay["rows"], T).reshape(T, heads, DV)
    return o.astype(jnp.float32), gd._heads_first(new, heads)


def _heads_first_scan(chunk, rows, state, cu, first_pos, seq_slot, seqs):
    """The reference: the layout the scan had before it took rows as they
    are.  Every operand gathered to tiles and TRANSPOSED to heads first
    (``[H, NT * C, d]``), the tiles scanned head by head with the same
    :func:`_chunk_math`, ``o`` transposed back and scattered."""
    q, k, v, g, b = rows
    T, heads = g.shape
    lay = la.tile_layout(cu, jnp.asarray([seqs]), first_pos, T, chunk)
    NT = lay["len"].shape[0]
    tiles = lambda a: la.to_tiles(a, lay["rows"]).swapaxes(0, 1)
    by_tile = lambda a: a.reshape(heads, NT, chunk, -1).transpose(1, 0, 2, 3)
    gt, bt = (tiles(a).astype(jnp.float32).reshape(heads, NT, chunk)
              for a in (g, b))
    aux = jnp.stack([jnp.cumsum(gt, axis=-1), bt], axis=2)
    slot = jnp.take(seq_slot, lay["seq"])

    def step(carry, x):
        st, run = carry
        q_n, k_n, v_n, aux_n, slot_n, init_n, len_n = x
        s_in = jnp.where(init_n == la.LOAD, st[slot_n],
                         jnp.where(init_n == la.ZERO, 0.0, run))
        o, s_out = jax.vmap(gd._chunk_math, in_axes=(0, 0, 0, 0, 0, None))(
            q_n, k_n, v_n, aux_n, s_in, len_n)
        live = jnp.logical_or(len_n > 0, init_n == la.LOAD)
        s_out = jnp.where(len_n > 0, s_out, s_in)
        st = st.at[jnp.where(live, slot_n, st.shape[0])].set(s_out,
                                                             mode="drop")
        return (st, s_out), o

    (new, _), o = jax.lax.scan(
        step, (state, jnp.zeros_like(state[0])),
        (by_tile(tiles(q)), by_tile(tiles(k)), by_tile(tiles(v)),
         aux.transpose(1, 0, 2, 3), slot, lay["init"], lay["len"]))
    o_t = o.transpose(1, 0, 2, 3).reshape(heads, NT * chunk, -1)
    o = la.from_tiles(o_t.astype(v.dtype).swapaxes(0, 1), lay["rows"], T)
    return o.astype(jnp.float32), new


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


# (T, cu_q_lens, first_pos, seq_slot, sequences, [(rows, slot, from zero)])
# of the multi-row sequences; SLOTS = 5
RAGGED = {
    # the multi-row sequence starts at packed row 3, no multiple of a tile
    "starts-off-a-tile": (
        210, [0, 1, 2, 3, 203, 204], [9, 9, 9, 70, 9], [4, 0, 2, 1, 3], 5,
        [((3, 203), 1, False)]),
    # a last tile of 40 / 8 rows with another sequence's rows BEHIND it in
    # the packed batch: what a tile row past ``len`` holds is zeros, not
    # the neighbour's rows
    "short-last-tile-rows-behind-it": (
        300, [0, 168, 169, 299, 299, 299], [0, 5, 128, 0, 0],
        [2, 4, 0, 1, 3], 3, [((0, 168), 2, True), ((169, 299), 0, False)]),
    # every sequence is one row: tile 0 copies a state onto itself
    "no-multi-row-sequence": (
        70, [0, 1, 2, 3, 3, 3], [5, 6, 7, 0, 0], [3, 1, 0, 2, 4], 3, []),
}


@pytest.mark.parametrize("heads", [2, 30])
@pytest.mark.parametrize("case", sorted(RAGGED))
@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_rows_as_they_are_give_the_heads_first_layouts_results(kind, case,
                                                               heads):
    """``[rows, H * d]`` tiles against the recurrence, and against the
    heads-first layout kept in this file: the twin to the bit, the kernel
    (two heads a step at 30 heads of 96 / 192: q's and k's block holds
    four, the last block two) within rounding."""
    T, cu, first_pos, seq_slot, seqs, multi = RAGGED[case]
    cu, first_pos, seq_slot = map(jnp.asarray, (cu, first_pos, seq_slot))
    chunk = 64
    rows, state = _rows(T, 11, heads=heads), _state(12, heads=heads)
    o, new = _ragged_scan(kind, chunk, rows, state, cu, first_pos, seq_slot,
                          seqs)
    ref_o, ref_new = _heads_first_scan(chunk, rows, state, cu, first_pos,
                                       seq_slot, seqs)
    if kind == "twin":
        assert (o == ref_o).all() and (new == ref_new).all()
    else:
        _close(o, ref_o, 2 * BF16)
        _close(new, ref_new, 1e-4)
    touched, live = set(), np.zeros((T,), bool)
    for (lo, hi), slot, zero in multi:
        start = jnp.zeros_like(state[slot]) if zero else state[slot]
        want, s = gd.recurrence(*(a[lo:hi] for a in rows), start)
        _close(o[lo:hi], want)
        _close(new[slot], s, 1e-4)
        touched.add(slot)
        live[lo:hi] = True
    for slot in set(range(SLOTS)) - touched:
        assert (new[slot] == state[slot]).all()
    assert (np.asarray(o)[~live] == 0).all()


@pytest.mark.parametrize("heads,dk,want", [
    (30, 96, (2, 2)), (32, 96, (2, 2)), (2, 96, (2, 1)), (1, 96, (1, 1)),
    (30, 128, (2, 1)), (32, 128, (2, 1)), (2, 128, (2, 1))])
def test_the_q_k_block_is_read_off_the_shapes(heads, dk, want):
    """Two heads a grid step; their ``2 x dk`` lanes are a q/k block of
    their own where they are whole lane tiles (128), else two steps' heads
    side by side are (96: ``4 x 96 = 3 x 128``), or the whole row where the
    heads are no more than a step's."""
    assert gd._scan_blocks(heads, dk) == want
    assert gd.scan_eligible(heads, dk, DV, jnp.bfloat16)
    hb, parts = want
    assert (parts * hb * dk) % 128 == 0 or parts * hb >= heads


@pytest.mark.parametrize("heads,dk", [
    pytest.param(6, 96, id="three-pairs-of-96-the-last-block-half-empty"),
    pytest.param(6, 128, id="three-pairs-of-128-a-block-a-step"),
    pytest.param(4, 96, id="two-pairs-of-96-one-whole-block"),
    pytest.param(2, 128, id="one-pair-the-whole-row")])
def test_the_kernel_takes_its_steps_half_of_a_q_k_block(heads, dk):
    """The interpret-mode kernel against the twin and the recurrence where
    a step's q and k are a select on a block of two steps' heads (an odd
    count of pairs: the last block lies half past the row's end) and where
    they are the block."""
    T, cu, first_pos, seq_slot, seqs, multi = RAGGED["starts-off-a-tile"]
    cu, first_pos, seq_slot = map(jnp.asarray, (cu, first_pos, seq_slot))
    rows = _rows(T, 31, heads=heads, dk=dk)
    state = _state(32, heads=heads, dk=dk)
    o, new = _ragged_scan("kernel", 64, rows, state, cu, first_pos, seq_slot,
                          seqs)
    twin_o, twin_new = _ragged_scan("twin", 64, rows, state, cu, first_pos,
                                    seq_slot, seqs)
    _close(o, twin_o, 2 * BF16)
    _close(new, twin_new, 1e-4)
    (lo, hi), slot, _ = multi[0]
    want, s = gd.recurrence(*(a[lo:hi] for a in rows), state[slot])
    _close(o[lo:hi], want)
    _close(new[slot], s, 1e-4)


@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_garbage_past_a_tiles_live_rows_moves_neither_o_nor_the_state(kind):
    """``rows_to_tiles`` leaves the batch's last row where a tile holds no
    token (``g`` and ``b`` come as zeros there); whatever q, k, v hold there
    (here infinities), ``o`` and the state are what zeros give, to the
    bit."""
    T, cu, first_pos, seq_slot, seqs, _ = RAGGED[
        "short-last-tile-rows-behind-it"]
    cu, first_pos, seq_slot = map(jnp.asarray, (cu, first_pos, seq_slot))
    q, k, v, g, b = _rows(T, 21)
    state = gd._heads_along_lanes(_state(22))
    lay = la.tile_layout(cu, jnp.asarray([seqs]), first_pos, T, 64)
    held = (lay["rows"] < T)[:, None]

    def scan(fill):
        tiles = lambda a: jnp.where(
            held, la.to_tiles(a.reshape(T, -1), lay["rows"]),
            jnp.asarray(fill, a.dtype))
        return _scan(kind, 64, tiles(q), tiles(k), tiles(v),
                     la.to_tiles(g, lay["rows"]), la.to_tiles(b, lay["rows"]),
                     state + 0, jnp.take(seq_slot, lay["seq"]), lay["init"],
                     lay["len"])

    (o, new), (o_inf, new_inf) = scan(0.0), scan(jnp.inf)
    assert bool(jnp.isfinite(o_inf.astype(jnp.float32)).all())
    assert (o == o_inf).all() and (new == new_inf).all()


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
