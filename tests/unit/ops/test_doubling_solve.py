"""The doubling both matrix-state scans share (``ops/gated_delta_rule.py``:
``levels``, ``rows_with`` / ``rows_spread`` / ``rows_plus``, ``solve``): a
level with ``s >= 8`` multiplies the ``C / 2`` rows that have bit ``s`` and
no others.  Held here: the halved levels against whole levels against
``numpy.linalg.inv`` in float64, at tiles of 32, 64 and 128 and over keys
that resemble one another (PR 50's case at keys of 8, where the product
form ``(I - A)(I + A^2)(I + A^4) ..`` lost three digits and more); which
levels are halved at which tile, as ``serving_stages()`` says it; and both
kernels under the interpreter against their twins and the recurrence at
each of the three tiles, over a batch whose sequences ``LOAD``, start from
``ZERO`` and carry, each with a last tile of fewer rows than the tile."""
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import gated_delta_rule as gd
from deepspeed_tpu.ops import kda
from tests.unit.ops import test_gated_delta_rule as delta_case
from tests.unit.ops import test_kda as kda_case

TILES = (32, 64, 128)
# (key width, share of every key that is one common direction)
KEYS = {"keys-apart": (96, 0.0), "keys-of-8-alike-0.9": (8, 0.9),
        "keys-of-8-alike-0.99": (8, 0.99)}


def _lower(C, d, alike, seed):
    """``tril(diag(b) (K K^T) exp(G_i - G_j), -1)`` in float64: unit keys;
    where they resemble one another ``b`` near 2 and hardly any decay, so
    the entries are near 2 everywhere."""
    r = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    k = unit((1 - alike) * r.normal(size=(C, d))
             + alike * np.sqrt(d) * unit(r.normal(size=(1, d))))
    b = r.uniform(*((1.8, 2.0) if alike else (0.0, 2.0)), size=(C, 1))
    big = np.cumsum(r.uniform(*((-1e-3, -1e-4) if alike else (-3.0, -1e-4)),
                              size=C))
    return np.tril(b * (k @ k.T) * np.exp(np.minimum(
        big[:, None] - big[None], 0.0)), -1)


@pytest.mark.parametrize("keys", sorted(KEYS))
@pytest.mark.parametrize("C", TILES)
def test_halved_levels_whole_levels_and_the_float64_inverse(C, keys,
                                                            monkeypatch):
    d, alike = KEYS[keys]
    a = _lower(C, d, alike, seed=C + d)
    want = np.linalg.inv(np.eye(C) + a)
    scale = np.abs(want).max()
    a32 = jnp.asarray(a, jnp.float32)
    halved = np.asarray(gd.solve(a32))
    assert gd.levels_halved(C)[0] > 0
    monkeypatch.setattr(gd, "_SUBLANES", 2 * C)       # no level is halved
    assert gd.levels_halved(C)[0] == 0
    whole = np.asarray(gd.solve(a32))
    # what a halved level drops are products with exact zeros
    assert np.abs(halved - whole).max() <= 2.0 ** -22 * scale
    for t in (halved, whole):
        assert np.abs(t - want).max() <= 2e-5 * scale
        assert (np.triu(t, 1) == 0).all() and (np.diag(t) == 1).all()


@pytest.mark.parametrize("C,solve,pairs", [
    (16, (1, 3), (1, 4)), (32, (2, 4), (2, 5)), (64, (3, 5), (3, 6)),
    (128, (4, 6), (4, 7))])
def test_a_level_is_halved_where_its_rows_are_whole_sublane_groups(
        C, solve, pairs):
    """``(halved, levels)`` of the solve (from blocks of 2 rows) and of
    KDA's level products (from 1): a level of ``s >= 8``."""
    assert gd.levels_halved(C) == solve
    assert gd.levels_halved(C, 1) == pairs
    assert [s for s, _ in gd.levels(C, 1)] == [
        1 << n for n in range(C.bit_length() - 1)]
    assert all(half == (s >= 8) for s, half in gd.levels(C, 1))
    assert gd.levels(C) == gd.levels(C, 1)[1:]


def test_a_tile_that_is_no_power_of_two_has_no_levels():
    with pytest.raises(AssertionError):
        gd.levels(96)


@pytest.mark.parametrize("s", [1, 2, 8, 16, 64])
def test_the_rows_a_level_couples_are_taken_and_put_back_in_order(s):
    C = 128
    x = jnp.arange(C * 3, dtype=jnp.float32).reshape(C, 3)
    has = (np.arange(C) & s) != 0
    up, lo = gd.rows_with(x, s), gd.rows_with(x, s, False)
    assert (np.asarray(up) == np.asarray(x)[has]).all()
    assert (np.asarray(lo) == np.asarray(x)[~has]).all()
    spread = np.asarray(gd.rows_spread(lo, s))
    assert (spread[~has] == np.asarray(lo)).all() and (spread[has] == 0).all()
    plus = np.asarray(gd.rows_plus(x, up, s))
    assert (plus[has] == 2 * np.asarray(up)).all()
    assert (plus[~has] == np.asarray(lo)).all()


# a batch of three sequences of which two hold several rows: one starts at
# position 0 in a slot that held another's state (ZERO, then carries), one
# continues a stored state (LOAD, then carries); 168 and 130 rows, so the
# last tile of each holds fewer rows than a tile of 32, 64 or 128
BATCH = delta_case.RAGGED["short-last-tile-rows-behind-it"]


def _batch():
    T, cu, first_pos, seq_slot, seqs, multi = BATCH
    return (T, jnp.asarray(cu), jnp.asarray(first_pos),
            jnp.asarray(seq_slot), seqs, multi)


def _held_to_the_recurrence(rule, o, new, rows, state, multi, T):
    live = np.zeros((T,), bool)
    for (lo, hi), slot, zero in multi:
        start = jnp.zeros_like(state[slot]) if zero else state[slot]
        want, s = rule.recurrence(*(a[lo:hi] for a in rows), start)
        delta_case._close(o[lo:hi], want)
        delta_case._close(new[slot], s, 1e-4)
        live[lo:hi] = True
    assert (np.asarray(o)[~live] == 0).all()


@pytest.mark.parametrize("chunk", TILES)
def test_the_delta_kernel_its_twin_and_the_recurrence_at_every_tile(chunk):
    T, cu, first_pos, seq_slot, seqs, multi = _batch()
    rows, state = delta_case._rows(T, 41), delta_case._state(42)
    run = lambda kind: delta_case._ragged_scan(
        kind, chunk, rows, state, cu, first_pos, seq_slot, seqs)
    (o, new), (twin_o, twin_new) = run("kernel"), run("twin")
    delta_case._close(o, twin_o, 2 * delta_case.BF16)
    delta_case._close(new, twin_new, 1e-4)
    for got in ((o, new), (twin_o, twin_new)):
        _held_to_the_recurrence(gd, *got, rows, state, multi, T)


@pytest.mark.parametrize("chunk", TILES)
def test_the_kda_kernel_its_twin_and_the_recurrence_at_every_tile(chunk):
    T, cu, first_pos, seq_slot, seqs, multi = _batch()
    rows, state = kda_case._rows(T, 43), kda_case._state(44)
    run = lambda kind: kda_case._ragged_scan(
        kind, chunk, rows, state, cu, first_pos, seq_slot, seqs)
    (o, new), (twin_o, twin_new) = run("kernel"), run("twin")
    kda_case._close(o, twin_o, 2 * kda_case.BF16)
    kda_case._close(new, twin_new, 1e-4)
    for got in ((o, new), (twin_o, twin_new)):
        _held_to_the_recurrence(kda, *got, rows, state, multi, T)


@pytest.mark.parametrize("case", [delta_case, kda_case],
                         ids=["delta", "kda"])
@pytest.mark.parametrize("chunk", TILES)
def test_the_twin_with_halved_levels_is_the_twin_with_whole_levels(
        case, chunk, monkeypatch):
    """Over the same batch, ``o`` and the state with the levels halved
    against no level halved: within two units in the last place of
    float32 at the results' scale (on this CPU: equal)."""
    T, cu, first_pos, seq_slot, seqs, _ = _batch()
    rows, state = case._rows(T, 45, dtype=jnp.float32), case._state(46)
    run = lambda: case._ragged_scan("twin", chunk, rows, state, cu,
                                    first_pos, seq_slot, seqs)
    o, new = run()
    monkeypatch.setattr(gd, "_SUBLANES", 2 * chunk)
    whole_o, whole_new = run()
    for got, want in ((o, whole_o), (new, whole_new)):
        assert float(jnp.abs(got - want).max()) <= 2.0 ** -22 * float(
            jnp.abs(want).max())
