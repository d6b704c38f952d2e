"""Kimi Delta Attention (``ops/kda.py``): the chunked scan and the one-token
step, each as its Pallas kernel under the interpreter and as its ``jax.numpy``
twin, against the recurrence token by token, the decay a VECTOR a key channel:
ragged tile lengths with a carried state (``test_gated_delta_rule.py``'s
layouts), chunk and level boundaries (tiles of 16, 32 and the published 128,
so every level product, every sublane roll and the running sum inside the
kernel are held), a sequence that starts at position 0 over a slot that held
another's state, slots with no row left alone, ``b`` near 0 and near 2,
channels whose decay UNDERFLOWS inside a chunk beside channels that hardly
decay, keys that resemble one another (where a solve through powers of ``A``
lost digits), garbage past a tile's live rows, and a decode block's eight
steps against eight recurrences."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import kda
from deepspeed_tpu.ops import linear_attention as la
from tests.unit.ops.test_gated_delta_rule import RAGGED, SLOTS

H, D = 2, 64
BF16 = 2.0 ** -8


def _rows(n, seed, dtype=jnp.bfloat16, g_range=(-3.0, -1e-4),
          b_range=(0.0, 2.0), heads=H, d=D, alike=0.0):
    """q and k of unit length a head (q / sqrt(d) besides), v of unit scale,
    g (a key channel) and b uniform over their ranges; ``alike``: the share
    of every key that is one common direction."""
    r = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(r.normal(size=(n, heads, d))) / np.sqrt(d)
    k = unit((1 - alike) * r.normal(size=(n, heads, d))
             + alike * np.sqrt(d) * unit(r.normal(size=(1, heads, d))))
    v = r.normal(size=(n, heads, d))
    g = r.uniform(*g_range, size=(n, heads, d))
    b = r.uniform(*b_range, size=(n, heads))
    cast = lambda a, t: jnp.asarray(a, jnp.float32).astype(t)
    return (cast(q, dtype), cast(k, dtype), cast(v, dtype),
            cast(g, jnp.float32), cast(b, jnp.float32))


def _state(seed, heads=H, d=D):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(SLOTS, heads, d, d)), jnp.float32)


def _scan(kind, chunk, *args):
    if kind == "twin":
        return kda.scan_twin(*args, chunk=chunk)
    return kda._scan(*args, chunk=chunk, interpret=True)


def _ragged_scan(kind, chunk, rows, state, cu, first_pos, seq_slot, seqs,
                 fill=None):
    """As ``paged/kda_state.py`` lays a tick out: rows ``[T, H * d]``, what
    a tile holds past its live rows the batch's last row (or ``fill``), g
    and b zeros there."""
    q, k, v, g, b = rows
    T, heads = b.shape
    lay = la.tile_layout(cu, jnp.asarray([seqs]), first_pos, T, chunk)
    held = (lay["rows"] < T)[:, None]
    tiles = lambda a: la.rows_to_tiles(a.reshape(T, -1), lay["rows"])
    if fill is not None:
        tiles = lambda a: jnp.where(                      # noqa: E731
            held, la.to_tiles(a.reshape(T, -1), lay["rows"]),
            jnp.asarray(fill, a.dtype))
    o_t, new = _scan(kind, chunk, tiles(q), tiles(k), tiles(v),
                     la.to_tiles(g.reshape(T, -1), lay["rows"]),
                     la.to_tiles(b, lay["rows"]),
                     kda._heads_along_lanes(state),
                     jnp.take(seq_slot, lay["seq"]), lay["init"], lay["len"])
    o = la.tiles_to_rows(o_t, lay["rows"], T).reshape(T, heads, -1)
    return o.astype(jnp.float32), kda._heads_first(new, heads)


def _close(got, want, tol=BF16):
    assert float(jnp.abs(got - want).max()) <= tol * max(
        float(jnp.abs(want).max()), 1e-3)


@pytest.mark.parametrize("chunk", [16, 32])
@pytest.mark.parametrize("case", sorted(RAGGED))
@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_the_chunked_scan_is_the_recurrence_over_a_ragged_batch(kind, case,
                                                                chunk):
    """Every multi-row sequence of a packed batch against its recurrence
    (carried state or zeros), tiles of 16 and 32 rows: four and five levels,
    last tiles ragged; slots without a row and rows without a tile are left
    alone."""
    T, cu, first_pos, seq_slot, seqs, multi = RAGGED[case]
    cu, first_pos, seq_slot = map(jnp.asarray, (cu, first_pos, seq_slot))
    rows, state = _rows(T, 11), _state(12)
    o, new = _ragged_scan(kind, chunk, rows, state, cu, first_pos, seq_slot,
                          seqs)
    touched, live = set(), np.zeros((T,), bool)
    for (lo, hi), slot, zero in multi:
        start = jnp.zeros_like(state[slot]) if zero else state[slot]
        want, s = kda.recurrence(*(a[lo:hi] for a in rows), start)
        _close(o[lo:hi], want)
        _close(new[slot], s, 1e-4)
        touched.add(slot)
        live[lo:hi] = True
    for slot in set(range(SLOTS)) - touched:
        assert (new[slot] == state[slot]).all()
    assert (np.asarray(o)[~live] == 0).all()


@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_the_published_tile_and_widths(kind):
    """Two heads of 128 x 128 at the kernel's own tile of 128 rows: seven
    levels, a whole-vreg roll at four of them, a head a whole lane tile; a
    sequence of two full tiles and a ragged third with a carried state."""
    T, d = 300, 128
    rows, state = _rows(T, 1, d=d), _state(2, d=d)
    o, new = _ragged_scan(kind, kda.CHUNK, rows, state, jnp.asarray([0, T]),
                          jnp.asarray([7]), jnp.asarray([3]), 1)
    want, s = kda.recurrence(*rows, state[3])
    _close(o, want)
    _close(new[3], s, 1e-4)
    assert kda.scan_eligible(64, 128, 128, jnp.bfloat16)
    assert kda.scan_heads(64, 128, 128) == 4
    assert kda.step_heads(64, 128) == 8
    assert not kda.scan_eligible(64, 128, 128, jnp.float32)


@pytest.mark.parametrize("g_range,b_range", [
    pytest.param((-60.0, -40.0), (1.9, 2.0), id="underflows-b-near-2"),
    pytest.param((-60.0, -1e-4), (0.0, 2.0), id="underflows-beside-none"),
    pytest.param((-1e-4, -1e-4), (1.9, 2.0), id="no-decay-b-near-2"),
    pytest.param((-1e-4, -1e-4), (0.0, 1e-3), id="no-decay-b-near-0"),
])
@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_the_scan_holds_at_the_ends_of_the_gates_ranges(kind, g_range,
                                                        b_range):
    """A channel at -40 a token is ``exp(-1280)`` across a tile of 32: it
    underflows inside the chunk, and beside it (the second case) lie channels
    that hardly decay.  Every exponent the scan takes is of a non-positive
    number, so nothing is ``inf * 0``; ``b`` near 2 makes the transition's
    eigenvalue along ``k`` nearly -a."""
    T = 100
    rows, state = _rows(T, 7, g_range=g_range, b_range=b_range), _state(8)
    o, new = _ragged_scan(kind, 32, rows, state, jnp.asarray([0, T]),
                          jnp.asarray([5]), jnp.asarray([2]), 1)
    want, s = kda.recurrence(*rows, state[2])
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(new).all())
    _close(o, want)
    _close(new[2], s, 1e-4)


@pytest.mark.parametrize("alike", [0.9, 0.99])
@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_keys_that_resemble_one_another_keep_their_digits(kind, alike):
    """Keys of 8 that share most of one direction, ``b`` near 2, hardly any
    decay, in float32: ``A``'s entries are near 2 everywhere and its powers
    grow by thousands, where the doubling solve is forward substitution by
    blocks and keeps float32's digits."""
    T, d = 64, 8
    rows = _rows(T, 5, dtype=jnp.float32, d=d, alike=alike,
                 g_range=(-1e-3, -1e-4), b_range=(1.8, 2.0))
    state = _state(6, d=d)
    if kind == "twin":
        o, new = _ragged_scan("twin", 32, rows, state, jnp.asarray([0, T]),
                              jnp.asarray([0]), jnp.asarray([1]), 1)
    else:
        # float32 rows are not the kernel's to take (Mosaic's layout): its
        # body under the interpreter all the same
        q, k, v, g, b = (a.reshape(T, -1) for a in rows)
        o_t, new = kda._scan(q, k, v, g, b, kda._heads_along_lanes(state),
                             jnp.asarray([1, 1]), jnp.asarray(
                                 [la.ZERO, la.CONTINUE]),
                             jnp.asarray([32, 32]), chunk=32, interpret=True)
        o, new = o_t.reshape(T, H, d), kda._heads_first(new, H)
    want, s = kda.recurrence(*rows, jnp.zeros_like(state[1]))
    _close(o, want, 1e-4)
    _close(new[1], s, 1e-4)


@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_garbage_past_a_tiles_live_rows_moves_neither_o_nor_the_state(kind):
    """Whatever q, k, v hold where a tile holds no token (here infinities),
    ``o`` and the state are what zeros give, to the bit."""
    T, cu, first_pos, seq_slot, seqs, _ = RAGGED[
        "short-last-tile-rows-behind-it"]
    cu, first_pos, seq_slot = map(jnp.asarray, (cu, first_pos, seq_slot))
    rows, state = _rows(T, 21), _state(22)
    scan = lambda fill: _ragged_scan(kind, 32, rows, state, cu, first_pos,
                                     seq_slot, seqs, fill=fill)
    (o, new), (o_inf, new_inf) = scan(0.0), scan(jnp.inf)
    assert bool(jnp.isfinite(o_inf).all())
    assert (o == o_inf).all() and (new == new_inf).all()


@pytest.mark.parametrize("kind", ["twin", "kernel"])
def test_a_decode_blocks_eight_steps_are_eight_recurrences(kind):
    """Three live rows of four, their slots out of order, one of them
    fresh (it reads zeros whatever its slot held): eight steps in place
    against eight recurrences, ``g`` as its projection leaves it (``[R, H *
    d]``); the idle slots' states do not move."""
    R = 4
    state = _state(2)
    live = jnp.asarray([True, False, True, True])
    order, n = la.live_first(live)
    slot = jnp.asarray([4, 0, 1, 3])[order]         # row -> slot
    rows = tuple(a.reshape((8, R) + a.shape[1:]) for a in _rows(8 * R, 3))
    run = kda._heads_along_lanes(state)
    outs = []
    for t in range(8):
        fresh = jnp.asarray([False, False, t == 0, False])[order]
        q, k, v, g, b = (a[t][order] for a in rows)
        if kind == "twin":
            o, run = kda.decode_step_twin(q, k, v, g, b, run, slot, fresh, n)
        else:
            o, run = kda._step(q, k, v, g, b, run, slot, fresh, n,
                               interpret=True)
        outs.append(jnp.zeros_like(o).at[order].set(o))
    outs = jnp.stack(outs).astype(jnp.float32)              # [8, R, H, D]
    run = kda._heads_first(run, H)
    for row, s, zero in ((0, 4, False), (2, 1, True), (3, 3, False)):
        start = jnp.zeros_like(state[s]) if zero else state[s]
        want, s_want = kda.recurrence(*(a[:, row] for a in rows), start)
        _close(outs[:, row], want)
        _close(run[s], s_want, 1e-4)
    assert (outs[:, 1] == 0).all()
    for idle in (0, 2):
        assert (run[idle] == state[idle]).all()


def test_the_entry_points_take_g_as_its_projection_leaves_it():
    """``decode_step`` reshapes ``g [R, H * d]``; on the CPU both entry
    points take their twins and say so (``ops/routes.py``)."""
    from deepspeed_tpu.ops import routes

    q, k, v, g, b = _rows(3, 6)
    state = kda._heads_along_lanes(_state(4))
    slot, fresh, n = (jnp.asarray([2, 0, 1]), jnp.zeros((3,), bool),
                      jnp.asarray([0]))
    o, new = kda.decode_step(q, k, v, g.reshape(3, -1), b, state + 0, slot,
                             fresh, n)
    assert (o == 0).all() and (new == state).all()
    o, new = kda._step(q, k, v, g, b, state + 0, slot, fresh, n,
                       interpret=True)
    assert (o == 0).all() and (new == state).all()
    assert routes.taken(prefix=kda.KERNEL)[kda.KERNEL_STEP] == routes.XLA
