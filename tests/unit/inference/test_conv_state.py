"""``inference/paged/conv_state.py:ragged_short_conv`` alone, against a
plain convolution a sequence written in NumPy: what a chunk's first rows
read (the slot's carried rows, zeros before position 0, never another
sequence's), whose state moves, and that the state is gathered a SEQUENCE,
never a token (PR 51).  And its ONE-PASS body (the kernel of
``ops/short_conv.py`` under the Pallas interpreter, the rows that start a
chunk set into its output; PR 67) against the lines it stands for: the
convolution above, then SiLU and the unit norms as
``models/olmo_hybrid.py:ShortConv`` writes them."""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.paged import ragged_short_conv
from deepspeed_tpu.inference.paged.conv_state import after_taps
from deepspeed_tpu.inference.paged.tree import SLOT_STATE
from deepspeed_tpu.ops import short_conv

C = 16


class Conv(nn.Module):
    @nn.compact
    def __call__(self, u, taps, pos, meta):
        return ragged_short_conv(self, u, taps, pos, meta)


def plain(u, taps, state, q_lens, starts, slots, seq_live):
    """A sequence at a time: its chunk behind the slot's rows (zeros where
    they lie before position 0), ``c_r = sum_k taps[:, k] x_{r + k}``; a
    live sequence leaves the last ``K - 1`` rows of (state, chunk)."""
    K = taps.shape[1]
    c = np.zeros_like(u)
    new = state.copy()
    at = 0
    for j, q in enumerate(q_lens):
        chunk = u[at:at + q]
        carried = state[slots[j]]
        before = carried * (starts[j] - (K - 1) + np.arange(K - 1)
                            >= 0)[:, None]
        x = np.concatenate([before, chunk])
        for r in range(q):
            c[at + r] = sum(taps[:, k] * x[r + k] for k in range(K))
        if seq_live[j]:
            new[slots[j]] = np.concatenate([carried, chunk])[-(K - 1):]
        at += q
    return c, new


def case(q_lens, starts, *, num_seqs=None, slots=None, pad=0, dead=()):
    """``q_lens`` and ``starts`` by packed sequence (every entry of
    ``max_seqs``); ``slots`` None is the decode form (no ``seq_slot``);
    ``pad`` rows past the last sequence; ``dead``: rows ``live`` is false
    at."""
    return dict(q_lens=q_lens, starts=starts, slots=slots, pad=pad,
                dead=dead,
                num_seqs=len(q_lens) if num_seqs is None else num_seqs)


def cases(K):
    perm = [2, 0, 3, 1]
    out = {f"a_chunk_of_{n}_rows": case([n, 1], [7, 30], slots=[1, 0])
           for n in sorted({1, 2, K - 1, K, 40})}
    out.update({
        # position 0 over a slot full of another tenant's rows: zeros
        "position_0_over_a_tenants_rows": case(
            [K + 2, 1, 2], [0, 0, 0], slots=[0, 2, 1]),
        # a chunk that goes on at position 5: the carried rows
        "continues_at_position_5": case([6, 1], [5, 1], slots=[0, 1], pad=2),
        "seq_slot_a_permutation": case(
            [3, 1, 9, 2], [4, 2, 0, 1], slots=perm),
        # no row, and past num_seqs: their slots' state unmoved to the bit
        "q_len_0_and_past_num_seqs": case(
            [4, 0, 2, 0], [3, 9, 1, 6], num_seqs=3, slots=perm, pad=1),
        "a_dead_last_row": case([5, 3], [2, 0], slots=[1, 0], dead=(7,)),
        "decode_no_seq_slot": case([1] * 5, [0, 1, 2, 3, 40], dead=(2,)),
        # no more rows than sequences, one of them a chunk of K rows
        "fewer_rows_than_sequences": case(
            [K, 0, 1, 0, 0, 0], [1, 0, 0, 0, 0, 0], num_seqs=3,
            slots=[4, 1, 5, 0, 2, 3]),
    })
    return out


IDS = [(K, name) for K in (3, 4) for name in cases(K)]


@pytest.mark.parametrize("K,name", IDS, ids=[f"K{K}-{n}" for K, n in IDS])
def test_the_convolution_is_the_plain_one_a_sequence(K, name):
    cs = cases(K)[name]
    q_lens, starts = np.asarray(cs["q_lens"]), np.asarray(cs["starts"])
    S, n = len(q_lens), cs["num_seqs"]
    T = int(q_lens.sum()) + cs["pad"]
    rng = np.random.default_rng(K * 1000 + len(name))
    u = rng.standard_normal((T, C)).astype(np.float32)
    taps = rng.standard_normal((C, K)).astype(np.float32)
    state = rng.standard_normal((S, K - 1, C)).astype(np.float32)
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    pos = np.zeros(T, np.int32)
    for j in range(S):
        pos[cu[j]:cu[j + 1]] = starts[j] + np.arange(q_lens[j])
    live = np.arange(T) < cu[n]
    live[list(cs["dead"])] = False
    meta = {"cu_q_lens": jnp.asarray(cu),
            "num_seqs": jnp.asarray([n], jnp.int32),
            "live": jnp.asarray(live)}
    slots = cs["slots"]
    if slots is None:
        assert T == S
        slots = list(range(S))
    else:
        meta["seq_slot"] = jnp.asarray(slots, jnp.int32)
    seq_live = [q_lens[j] > 0 and j < n and live[cu[j + 1] - 1]
                for j in range(S)]
    want, want_state = plain(u, taps, state, q_lens, starts, slots, seq_live)

    got, v = Conv().apply({"cache": {SLOT_STATE: jnp.asarray(state)}},
                          jnp.asarray(u), jnp.asarray(taps),
                          jnp.asarray(pos), meta, mutable=["cache"])
    rows = live & (np.arange(T) < cu[S])
    np.testing.assert_allclose(np.asarray(got)[rows], want[rows],
                               rtol=0, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(v["cache"][SLOT_STATE]),
                                  want_state)


def _avals(jaxpr):
    """Every value of a jaxpr and of the jaxprs its equations hold."""
    for v in [*jaxpr.invars, *jaxpr.constvars]:
        yield v.aval
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_no_copy_of_the_state_a_token_is_built():
    """The fused tick's shape at a narrow width: no value of the program
    is as large as ``[T, K - 1, C]``, so the gather by token cannot come
    back unseen."""
    T, S, K, width = 1032, 8, 4, 256
    u = jax.ShapeDtypeStruct((T, width), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((width, K), jnp.bfloat16)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)

    def run(state, u, taps, pos, cu, num_seqs, seq_slot):
        meta = {"cu_q_lens": cu, "num_seqs": num_seqs, "seq_slot": seq_slot,
                "live": jnp.arange(T) < cu[num_seqs[0]]}
        return Conv().apply({"cache": {SLOT_STATE: state}}, u, taps, pos,
                            meta, mutable=["cache"])

    jaxpr = jax.make_jaxpr(run)(
        jax.ShapeDtypeStruct((S, K - 1, width), jnp.bfloat16), u, taps,
        i32(T), i32(S + 1), i32(1), i32(S))
    sizes = [a.size for a in _avals(jaxpr.jaxpr) if hasattr(a, "size")]
    assert max(sizes) >= T * width          # the walk saw the rows
    assert max(sizes) < T * (K - 1) * width, (
        "a value of [T, K - 1, C] elements or more: the state is copied a "
        "token again")


class Mixer(nn.Module):
    """q~, k~, v~ through the convolution, SiLU and the unit norms of q and
    k: in one pass (the kernel interpreted, the norms inside it), or by the
    lines the pass stands for."""

    one_pass: bool
    dk: int
    silu: bool

    @nn.compact
    def __call__(self, parts, taps, pos, meta):
        unit = ((self.dk, 1e-6, (self.dk ** -0.5, 1.0, None))
                if len(parts) == 3 else None)
        norm = lambda outs: tuple(after_taps(a, False, unit, n)
                                  for n, a in enumerate(outs))
        if self.one_pass:
            return ragged_short_conv(self, parts, taps, pos, meta,
                                     silu=self.silu, unit=unit,
                                     interpret=True)
        c = ragged_short_conv(self, jnp.concatenate(parts, -1), taps, pos,
                              meta)
        if self.silu:
            c = jax.nn.silu(c.astype(jnp.float32)).astype(c.dtype)
        cuts = np.cumsum([p.shape[1] for p in parts])[:-1]
        return norm(jnp.split(c, cuts, axis=-1))


def ticks(K):
    """Fused ticks by name: ``q_lens`` and ``starts`` by packed sequence,
    with rows past the last sequence, dead slots and dead rows."""
    perm = [2, 0, 3, 1, 5, 4]
    return {
        # one chunk from row 0 over the kernel's row blocks, decode rows
        # behind it
        "aligned": case([520, 1, 1, 1], [300, 7, 0, 2], slots=[1, 0, 3, 2],
                        pad=5),
        # a tail beside the next prompt's head, which starts at position 0
        # in a slot with a previous tenant
        "boundary": case([300, 212, 1, 1, 1, 1], [77, 0, 3, 0, 9, 1],
                         slots=perm, pad=4),
        "a_tail_alone": case([37], [1000], slots=[0], pad=3),
        "one_row_sequences_beside_chunks": case(
            [1, 2, 1, K - 1, 1, K + 1], [0, 5, 3, 0, 8, 2], slots=perm,
            pad=2),
        "dead_slots_and_dead_rows": case(
            [9, 0, 3, 0, 2, 0], [4, 0, 0, 0, 6, 0], num_seqs=5, slots=perm,
            pad=2, dead=(8, 11)),
    }


def _spacing(x):
    """The distance between neighbouring bf16 numbers at ``|x|``."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 1e-30))) - 7)


HEADS = {"heads_128": (2, 128, 128), "heads_96_192": (2, 96, 192),
         "one_part_no_silu": (1, 256, 0)}
PASSES = [(K, h, t) for K in (3, 4) for h in HEADS for t in ticks(K)]


@pytest.mark.parametrize("K,heads,tick", PASSES,
                         ids=[f"K{K}-{h}-{t}" for K, h, t in PASSES])
def test_one_pass_is_the_lines_it_stands_for(K, heads, tick):
    assert short_conv.eligible(520, K, jnp.bfloat16, HEADS[heads][1])
    """Every row of the tick (the rows past the last sequence too) within
    two neighbouring bf16 numbers of the twin's, all but a few in a
    thousand equal to the bit (SiLU's quotient is an estimate and a Newton
    step in the kernel), and the state left in every slot equal."""
    cs = ticks(K)[tick]
    H, dk, dv = HEADS[heads]
    q_lens, starts = np.asarray(cs["q_lens"]), np.asarray(cs["starts"])
    S, n = len(q_lens), cs["num_seqs"]
    T = -(-(int(q_lens.sum()) + cs["pad"]) // 8) * 8
    rng = np.random.default_rng(K * 100 + len(tick) + dk)
    bf = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    widths = (H * dk, H * dk, H * dv) if dv else (H * dk,)
    parts = tuple(bf(T, w) for w in widths)
    taps, state = bf(sum(widths), K), bf(S, K - 1, sum(widths))
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    pos = np.zeros(T, np.int32)
    for j in range(S):
        pos[cu[j]:cu[j + 1]] = starts[j] + np.arange(q_lens[j])
    live = np.arange(T) < cu[n]
    live[list(cs["dead"])] = False
    meta = {"cu_q_lens": jnp.asarray(cu),
            "num_seqs": jnp.asarray([n], jnp.int32),
            "live": jnp.asarray(live),
            "seq_slot": jnp.asarray(cs["slots"], jnp.int32)}
    run = lambda one: Mixer(one, dk, bool(dv)).apply(
        {"cache": {SLOT_STATE: state}}, parts, taps, jnp.asarray(pos), meta,
        mutable=["cache"])
    (want, want_v), (got, got_v) = run(False), run(True)
    for a, b in zip(want, got):
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        assert np.all(np.abs(a - b) <= 2 * _spacing(np.maximum(
            np.abs(a), np.abs(b))))
        assert np.mean(a != b) < 5e-3
    np.testing.assert_array_equal(
        np.asarray(got_v["cache"][SLOT_STATE].astype(jnp.float32)),
        np.asarray(want_v["cache"][SLOT_STATE].astype(jnp.float32)))
