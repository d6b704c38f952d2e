"""Decaying linear attention (``ops/linear_attention.py``): the chunked
scan and the one-token step, each as its Pallas kernel under the
interpreter and as its ``jax.numpy`` twin, against the recurrence token by
token: ragged batches in tiles with a carried state, a sequence that
starts at position 0 over a slot that held another's state, slots with no
row left alone, and a decode block's eight steps against eight
recurrences.  The scan takes its rows as the projections leave them
(``[rows, H * d]``, a head a lane block): the parent's heads-first layout
(``to_tiles(..).transpose``) is kept HERE as the reference its results are
held to, to the bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import linear_attention as la

H, D, SLOTS = 3, 128, 5


def _rows(n, seed, dtype=jnp.bfloat16, scale=1.0, heads=H):
    r = np.random.default_rng(seed)
    return tuple(jnp.asarray(r.normal(size=(n, heads, D)) * s, jnp.float32
                             ).astype(dtype)
                 for s in (0.09 * scale, scale, scale))


def _scan(kind, *args):
    q, k, v, ld, state, slot, init, length = args
    if kind == "twin":
        return la.scan_twin(q, k, v, ld, state, slot, init, length)
    return la._scan(q, k, v, la._lam_lanes(ld), state, slot, init, length,
                    interpret=True)


def _ragged_scan(kind, q, k, v, ld, state, cu, first_pos, seq_slot, seqs):
    T, heads = q.shape[:2]
    lay = la.tile_layout(cu, jnp.asarray([seqs]), first_pos, T, la.CHUNK)
    # as ``paged/linear_state.py`` lays them out: what a tile holds past its
    # live rows is the batch's last row, and the scan zeroes it
    tiles = lambda a: la.rows_to_tiles(a.reshape(T, -1), lay["rows"])
    o_t, new = _scan(kind, tiles(q), tiles(k), tiles(v), ld, state + 0,
                     jnp.take(seq_slot, lay["seq"]), lay["init"], lay["len"])
    o = la.tiles_to_rows(o_t, lay["rows"], T).reshape(T, heads, D)
    return o.astype(jnp.float32), new


def _heads_first_scan(q, k, v, ld, state, cu, first_pos, seq_slot, seqs):
    """The reference: the layout the scan had before it took rows as they
    are.  Every operand gathered to tiles and TRANSPOSED to heads first
    (``[H, NT * C, d]``), the tiles scanned head by head with the same
    :func:`_chunk_math`, ``o`` transposed back and scattered."""
    T, heads = q.shape[:2]
    C = la.CHUNK
    lay = la.tile_layout(cu, jnp.asarray([seqs]), first_pos, T, C)
    NT = lay["len"].shape[0]
    tiles = lambda a: la.to_tiles(a, lay["rows"]).transpose(1, 0, 2).reshape(
        heads, NT, C, D).transpose(1, 0, 2, 3)
    math = jax.vmap(la._chunk_math, in_axes=(0, 0, 0, 0, 0, None))
    lam = ld.astype(jnp.float32)

    def step(carry, x):
        st, run = carry
        q_n, k_n, v_n, slot_n, init_n, len_n = x
        s_in = jnp.where(init_n == la.LOAD, st[slot_n],
                         jnp.where(init_n == la.ZERO, 0.0, run))
        o, s_out = math(q_n, k_n, v_n, lam, s_in, len_n)
        live = jnp.logical_or(len_n > 0, init_n == la.LOAD)
        s_out = jnp.where(len_n > 0, s_out, s_in)
        st = st.at[jnp.where(live, slot_n, st.shape[0])].set(s_out,
                                                             mode="drop")
        return (st, s_out), o

    (new, _), o = jax.lax.scan(
        step, (state, jnp.zeros_like(state[0])),
        (tiles(q), tiles(k), tiles(v), jnp.take(seq_slot, lay["seq"]),
         lay["init"], lay["len"]))
    o_t = o.transpose(1, 0, 2, 3).reshape(heads, NT * C, D).astype(q.dtype)
    o = la.from_tiles(o_t.transpose(1, 0, 2), lay["rows"], T)
    return o.astype(jnp.float32), new


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


@pytest.mark.parametrize("case", ["two-sequences", "no-multi-row-sequence"])
def test_rows_go_to_tiles_and_back_by_gathers(case):
    """``rows_to_tiles`` holds a tile's live rows where ``to_tiles`` does
    (past them the batch's last row, for the scans to zero), and
    ``tiles_to_rows`` is ``from_tiles`` wherever the tiles without a token
    are zeros: the last tile never holds one."""
    T = 300
    cu = jnp.asarray([0, 168, 169, 299, 299, 299] if case == "two-sequences"
                     else [0, 1, 2, 3, 3, 3])
    lay = la.tile_layout(cu, jnp.asarray([3]), jnp.zeros((5,), jnp.int32), T,
                         la.CHUNK)
    rows, n = lay["rows"], int(lay["n"][0])
    assert n < lay["len"].shape[0]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(T, 6)), jnp.float32)
    filled, clipped = la.to_tiles(x, rows), la.rows_to_tiles(x, rows)
    live = (rows < T)[:, None]
    assert (jnp.where(live, clipped, 0.0) == filled).all()
    assert (jnp.where(live, 0.0, clipped) == jnp.where(live, 0.0, x[-1])
            ).all()
    y = jnp.asarray(np.random.default_rng(4).normal(size=filled.shape),
                    jnp.float32).at[n * la.CHUNK:].set(0.0)
    assert (la.tiles_to_rows(y, rows, T) == la.from_tiles(y, rows, T)).all()


# (T, rows a sequence): the tick of the two ``longdoc16k-closed`` cells (a
# chunk of 1,024 rows in packed rows 3-1,026 beside seven one-row sequences,
# the last row padding), a row a sequence, and as many tiles as eight
# sequences can fill
TICKS = {
    "a-chunk-of-1024-beside-seven-rows": (1032, [1, 1, 1, 1024, 1, 1, 1, 1]),
    "a-row-a-sequence": (8, [1] * 8),
    "eight-sequences-of-129-rows": (1032, [129] * 8),
}


def _tick_layout(case):
    T, lens = TICKS[case]
    cu = np.concatenate([[0], np.cumsum(lens + [0] * (8 - len(lens)))])
    lay = la.tile_layout(jnp.asarray(cu, jnp.int32), jnp.asarray([len(lens)]),
                         jnp.zeros((8,), jnp.int32), T, la.CHUNK)
    held = np.zeros((T,), bool)
    for lo, n in zip(cu, lens):
        held[lo:lo + n] = n >= 2
    return T, lay, held


@pytest.mark.parametrize("case", sorted(TICKS))
def test_the_last_tile_of_a_layout_is_never_live(case):
    """``ceil(T / tile) + seqs`` tiles are more than ``T`` rows of ``seqs``
    sequences can fill, so the last holds no token: ``tiles_to_rows`` reads
    its zeros there."""
    T, lay, held = _tick_layout(case)
    NT = lay["len"].shape[0]
    assert NT == -(-T // la.CHUNK) + 8
    assert int(lay["n"][0]) < NT and int(lay["len"][-1]) == 0
    assert (np.asarray(lay["rows"])[-la.CHUNK:] == T).all()
    assert int(jnp.sum(lay["len"])) == held.sum()


@pytest.mark.parametrize("case", sorted(TICKS))
def test_a_packed_row_no_tile_holds_reads_zeros(case):
    """The way back is a gather: a row of a multi-row sequence reads the tile
    row that holds it, a one-row sequence's row and the padding read zeros,
    whatever the live tiles hold past their ``len``."""
    T, lay, held = _tick_layout(case)
    n = int(lay["n"][0])
    y = jnp.ones((lay["rows"].shape[0], 6), jnp.float32
                 ).at[n * la.CHUNK:].set(0.0)
    o = np.asarray(la.tiles_to_rows(y, lay["rows"], T))
    assert (o[held] == 1).all() and (o[~held] == 0).all()
    x = jnp.asarray(np.random.default_rng(5).normal(size=(T, 6)), jnp.float32)
    back = la.tiles_to_rows(
        jnp.where((lay["rows"] < T)[:, None],
                  la.rows_to_tiles(x, lay["rows"]), 0.0), lay["rows"], T)
    assert (back == jnp.where(held[:, None], x, 0.0)).all()


@pytest.mark.parametrize("heads,want", [(32, 4), (30, 2), (2, 2), (3, 1)])
def test_a_grid_step_of_the_scan_holds_the_heads_that_divide_them(heads,
                                                                  want):
    assert la.scan_heads(heads) == want


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
    o, new = _ragged_scan(kind, q, k, v, ld, state, cu, first_pos, seq_slot,
                          3)
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


# (T, cu_q_lens, first_pos, seq_slot, sequences, [(rows, slot, from zero)])
# of the multi-row sequences; SLOTS = 5
RAGGED = {
    # the multi-row sequence starts at packed row 3, no multiple of a tile
    "starts-off-a-tile": (
        300, [0, 1, 2, 3, 293, 294], [9, 9, 9, 70, 9], [4, 0, 2, 1, 3], 5,
        [((3, 293), 1, False)]),
    # a last tile of 40 / 2 rows with another sequence's rows BEHIND it in
    # the packed batch: what a tile row past ``len`` holds is zeros, not
    # the neighbour's rows
    "short-last-tile-rows-behind-it": (
        300, [0, 168, 169, 299, 299, 299], [0, 5, 128, 0, 0],
        [2, 4, 0, 1, 3], 3, [((0, 168), 2, True), ((169, 299), 0, False)]),
    # every sequence is one row: tile 0 copies a state onto itself
    "no-multi-row-sequence": (
        70, [0, 1, 2, 3, 3, 3], [5, 6, 7, 0, 0], [3, 1, 0, 2, 4], 3, []),
}


@pytest.mark.parametrize("heads", [3, 32])
@pytest.mark.parametrize("case", sorted(RAGGED))
@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_rows_as_they_are_give_the_heads_first_layouts_results(kind, case,
                                                               heads):
    """``[rows, H * d]`` tiles against the recurrence, and against the
    heads-first layout kept in this file: the twin to the bit, the kernel
    (a head's 128 lanes a grid step) within rounding."""
    T, cu, first_pos, seq_slot, seqs, multi = RAGGED[case]
    cu, first_pos, seq_slot = map(jnp.asarray, (cu, first_pos, seq_slot))
    q, k, v = _rows(T, 11, heads=heads)
    ld = la.head_log_decay(heads)
    state = jnp.asarray(np.random.default_rng(12).normal(
        size=(SLOTS, heads, D, D)), jnp.float32)
    o, new = _ragged_scan(kind, q, k, v, ld, state, cu, first_pos, seq_slot,
                          seqs)
    ref_o, ref_new = _heads_first_scan(q, k, v, ld, state, cu, first_pos,
                                       seq_slot, seqs)
    bf16 = 2.0 ** -8
    if kind == "twin":
        assert (o == ref_o).all() and (new == ref_new).all()
    else:
        assert float(jnp.abs(o - ref_o).max()) <= 2 * bf16 * max(
            float(jnp.abs(ref_o).max()), 1e-3)
        assert float(jnp.abs(new - ref_new).max()) < 1e-4
    touched, live = set(), np.zeros((T,), bool)
    for (lo, hi), slot, zero in multi:
        start = jnp.zeros_like(state[slot]) if zero else state[slot]
        want, s = la.recurrence(q[lo:hi], k[lo:hi], v[lo:hi], ld, start)
        assert float(jnp.abs(o[lo:hi] - want).max()) <= bf16 * float(
            jnp.abs(want).max())
        assert float(jnp.abs(new[slot] - s).max()) < 1e-4
        touched.add(slot)
        live[lo:hi] = True
    for slot in set(range(SLOTS)) - touched:
        assert (new[slot] == state[slot]).all()
    assert (np.asarray(o)[~live] == 0).all()


@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_garbage_past_a_tiles_live_rows_moves_neither_o_nor_the_state(kind):
    """``rows_to_tiles`` leaves the batch's last row where a tile holds no
    token; whatever lies there (here infinities), ``o`` and the state are
    what zeros give, to the bit."""
    T, cu, first_pos, seq_slot, seqs, _ = RAGGED[
        "short-last-tile-rows-behind-it"]
    cu, first_pos, seq_slot = map(jnp.asarray, (cu, first_pos, seq_slot))
    q, k, v = _rows(T, 21)
    ld = la.head_log_decay(H)
    state = jnp.asarray(np.random.default_rng(22).normal(
        size=(SLOTS, H, D, D)), jnp.float32)
    lay = la.tile_layout(cu, jnp.asarray([seqs]), first_pos, T, la.CHUNK)
    held = (lay["rows"] < T)[:, None]

    def scan(fill):
        tiles = lambda a: jnp.where(
            held, la.to_tiles(a.reshape(T, -1), lay["rows"]),
            jnp.asarray(fill, a.dtype))
        return _scan(kind, tiles(q), tiles(k), tiles(v), ld, state + 0,
                     jnp.take(seq_slot, lay["seq"]), lay["init"], lay["len"])

    (o, new), (o_inf, new_inf) = scan(0.0), scan(jnp.inf)
    assert bool(jnp.isfinite(o_inf.astype(jnp.float32)).all())
    assert (o == o_inf).all() and (new == new_inf).all()


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


# -- a tick whose chunks start on row tiles IS its tile layout ---------------

# the three matrix-state rules, each at a tile of its own: (tile, heads, dk,
# dv, the width of g: 0 none, 1 a number a head, dk a key channel)
RULES = {"linear": (128, 2, 128, 128, 0), "delta": (64, 2, 64, 128, 1),
         "kda": (32, 2, 64, 64, 64)}
# rows a sequence of a tick of ``3 tiles + 8`` rows, in tiles; and whether
# the tiles are the tick's own rows
ALIGNED = {
    "whole-tiles-beside-decode-rows": ([2.0, 1, 1, 1], True),
    "two-chunks-of-whole-tiles": ([1.0, 2.0, 1], True),
    # the decode rows lie in the tile the tail leaves part empty
    "a-tail-behind-whole-tiles": ([2.35, 1, 1], True),
    "no-multi-row-sequence": ([1, 1, 1, 1], True),
    # its last rows lie in the eight past the whole tiles
    "a-chunk-that-ends-past-the-whole-tiles": ([3.04], False),
    "two-tails": ([1.6, 0.8, 1], False),
    "a-row-between-two-chunks": ([1.0, 1, 1.0], False),
}


def _tick(rule, case, first_pos):
    from deepspeed_tpu.ops import gated_delta_rule as gd, kda

    tile, heads, dk, dv, gw = RULES[rule]
    T, S = 3 * tile + 8, 5
    lens = [n if isinstance(n, int) else int(n * tile)
            for n in ALIGNED[case][0]]
    cu = jnp.asarray(np.concatenate(
        [[0], np.cumsum(lens + [0] * (S - len(lens)))]), jnp.int32)
    r = np.random.default_rng(len(case))
    bf = lambda *s: jnp.asarray(r.normal(size=s) * 0.3, jnp.float32).astype(
        jnp.bfloat16)
    rows = (bf(T, heads * dk), bf(T, heads * dk), bf(T, heads * dv))
    gates = ()
    if gw:
        g = jnp.asarray(r.uniform(-2.0, -1e-3, size=(T, heads * gw)),
                        jnp.float32)
        b = jnp.asarray(r.uniform(0.1, 1.9, size=(T, heads)), jnp.float32)
        rows, gates = (rows + (g,), (b,)) if gw > 1 else (rows, (g, b))
    shape = (S, heads, dk, dv) if rule == "linear" else (S, dk, heads * dv)
    state = jnp.asarray(r.normal(size=shape), jnp.float32)
    lay = la.tile_layout(cu, jnp.asarray([len(lens)]),
                         jnp.full((S,), first_pos, jnp.int32), T, tile)
    ld = la.head_log_decay(heads)

    def scan(kind):
        def run(rows, gates, st, *tiles):
            if rule == "linear":
                return la.chunk_scan(*rows, ld, st, *tiles, interpret=kind)
            q, k, v, g = rows if gw > 1 else rows + gates[:1]
            return (kda if gw > 1 else gd).chunk_scan(
                q, k, v, g, gates[-1], st, *tiles, chunk=tile,
                interpret=kind)
        return run

    held = np.zeros((T,), bool)
    for lo, n in zip(np.asarray(cu), lens):
        held[lo:lo + n] = n >= 2
    return T, tile, lay, scan, rows, gates, state, held


@pytest.mark.parametrize("first_pos", [0, 640])
@pytest.mark.parametrize("case", sorted(ALIGNED))
@pytest.mark.parametrize("rule,kind", [
    ("linear", "twin"), ("delta", "twin"), ("kda", "twin"),
    ("linear", "kernel"), ("delta", "kernel"), ("kda", "kernel")])
def test_a_tick_read_as_its_own_tiles_is_the_gathered_tick_to_the_bit(
        rule, kind, case, first_pos):
    """``scan_tick`` where ``tile_layout`` says the tiles ARE the tick's rows
    (no row moved, ``T // tile`` tiles) against the same tick gathered to
    ``ceil(T / tile) + seqs`` tiles and back: ``o``, every slot's state and
    the zeros in the rows no tile holds, equal to the bit."""
    T, tile, lay, scan, rows, gates, state, held = _tick(rule, case,
                                                         first_pos)
    assert bool(lay["aligned"]) == ALIGNED[case][1]
    slots = jnp.asarray([3, 0, 4, 1, 2], jnp.int32)
    run = jax.jit(lambda lay, st: la.scan_tick(
        scan(kind == "kernel"), lay, slots, T, tile, rows, gates, st))
    o, new = run(lay, state + 0)
    o_g, new_g = run({**lay, "aligned": jnp.asarray(False)}, state + 0)
    assert o.shape == o_g.shape == (T, rows[2].shape[1])
    assert (o == o_g).all() and (new == new_g).all()
    assert (np.asarray(o, np.float32)[~held] == 0).all()
    if held.any():
        assert (np.asarray(o, np.float32)[held] != 0).any()
        assert (new != state).any()
