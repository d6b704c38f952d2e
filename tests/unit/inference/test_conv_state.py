"""``inference/paged/conv_state.py:ragged_short_conv`` alone, against a
plain convolution a sequence written in NumPy: what a chunk's first rows
read (the slot's carried rows, zeros before position 0, never another
sequence's), whose state moves, and that the state is gathered a SEQUENCE,
never a token (PR 51)."""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.paged import ragged_short_conv
from deepspeed_tpu.inference.paged.tree import SLOT_STATE

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
