"""The plan of a fused tick hands prompt rows out in whole row tiles and
packs them from row 0 of the tick (``ragged_engine.py``: ``ROW_TILE``,
``_plan_rows``, ``_pack_tick``).

- the seven chunk kernels under ``ops/`` tile rows by the planner's constant;
- a take is the prompt's rest, or whole tiles where that gives up a few rows
  only (1,024 of a tick of 1,032 whatever the decode rows; 541 to 544 of a
  tick of 544 as ever, 512 where 513 to 520 are left), the packed order is
  whole-tile chunks from row 0, the others, one-row sequences, and every
  array indexed by packed sequence or by row agrees with a plain
  re-derivation from the requests (both page tables of a window model);
- whether a tick's chunks lie in its own row tiles is one rule on the host
  (``tiles_aligned``, counted into ``aligned_tick_share``) and on the device
  (``tile_layout``'s ``aligned``, where a matrix-state layer then gathers
  nothing), and a tick of Solar's tiny model serves the same tokens either
  way;
- a prompt that crosses three ticks beside a decode row serves the reference's
  tokens, and ``serving_stages()["fused_tick_host"]`` says how many rows a
  spanned tile held.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.ragged_engine import (
    ROW_TILE, ROWS_GIVEN_UP, RaggedInferenceEngineV2, tiles_aligned,
    whole_tiles)
from deepspeed_tpu.models import lfm2_moe
from deepspeed_tpu.models import trinity as tr
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_config
from deepspeed_tpu.ops import (block_select_attention, gated_delta_rule, kda,
                               latent_attention, linear_attention,
                               paged_prefill, token_select_attention)

CFG = get_config("tinyllama", vocab_size=64, hidden_size=32,
                 intermediate_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=8192, dtype=jnp.float32,
                 param_dtype=jnp.float32, scan_layers=True, remat=False,
                 use_flash_attention=False)
TRINITY = tr.get_config("tinytrinity", dtype=jnp.float32,
                        param_dtype=jnp.float32, remat=False,
                        use_flash_attention=False)


@pytest.mark.parametrize("name,tiles", [
    ("gated_delta_rule.CHUNK", gated_delta_rule.CHUNK),
    ("kda.CHUNK", kda.CHUNK),
    ("linear_attention.CHUNK", linear_attention.CHUNK),
    ("block_select_attention.TILE", block_select_attention.TILE),
    ("token_select_attention.TILE", token_select_attention.TILE),
    ("latent_attention._TILE_TOKENS", latent_attention._TILE_TOKENS),
    # by query group: the least a tile holds, and every other a multiple
    ("paged_prefill._TILE_TOKENS", paged_prefill._TILE_TOKENS),
])
def test_a_chunk_kernels_row_tile_is_the_planners(name, tiles):
    tiles = tiles if isinstance(tiles, tuple) else (tiles,)
    assert min(tiles) == ROW_TILE, name
    assert all(t % ROW_TILE == 0 for t in tiles), name


@pytest.mark.parametrize("rest,room,first,want", [
    (700, 1032, True, 700),         # the rest fits: whole
    (5000, 1032, True, 1024),       # eight rows given up for a ninth tile
    (5000, 1025, True, 1024),
    (5000, 1024, True, 1024),
    (5000, 544, True, 544),         # thirty-two are too many to give up
    (5000, 521, True, 521),
    (5000, 520, True, 512),
    (5000, 513, False, 512),
    (5000, 727, False, 727),        # beside another prompt's tail
    (5000, 640 + ROWS_GIVEN_UP, False, 640),
    (5000, 6, False, 0),            # what a whole chunk leaves goes to no one
    (5000, 6, True, 6),             # but a tick's first chunk is never nothing
    (5000, 10, True, 10),           # a tiny engine plans as it did
    (5000, 0, True, 0),
])
def test_the_rule_a_row_at_a_time(rest, room, first, want):
    assert whole_tiles(rest, room, first) == want


# -- the plan and the pack against a plain re-derivation ----------------------

@pytest.fixture(scope="module")
def llama_params():
    return jax.jit(LlamaForCausalLM(CFG).init)(
        jax.random.PRNGKey(7), np.zeros((1, 8), np.int32))


@pytest.fixture(scope="module")
def trinity_params():
    return jax.jit(tr.TrinityForCausalLM(TRINITY).init)(
        jax.random.PRNGKey(3), np.zeros((1, 8), np.int32))["params"]


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 60, size=n).astype(
        np.int32)


def _seat(eng, decoding, prefilling):
    """Admit ``len(decoding)`` sequences that decode (prompts of those
    lengths, one token generated) and then the ``prefilling`` ones, each
    ``(prompt length, rows already cached)``; returns the prefilling uids."""
    for k, n in enumerate(decoding):
        eng.put_request(_tokens(n, k), max_new_tokens=8)
    uids = [eng.put_request(_tokens(n, 100 + k), max_new_tokens=8)
            for k, (n, _) in enumerate(prefilling)]
    eng._admit()
    assert not eng.waiting
    done = dict(zip(uids, (d for _, d in prefilling)))
    for r in eng.slots:
        if r is None:
            continue
        if r.uid in done:
            r.prefill_done = done[r.uid]
        else:
            r.prefill_done = r.ctx_len
            r.generated = [3 + r.slot]
            eng.last_tokens[r.slot] = 3 + r.slot
    return uids


def _want_takes(eng, decode_rows):
    """The rule, plainly: by uid, the rest where the tick's rows hold it,
    else those rows less a part tile of a few; no pool in sight."""
    budget, takes = eng.T - decode_rows, {}
    for r in sorted((r for r in eng.slots
                     if r is not None and r.prefill_done < r.ctx_len),
                    key=lambda r: r.uid):
        rest = r.ctx_len - r.prefill_done
        take = budget
        if rest <= budget:
            take = rest
        elif budget % ROW_TILE <= ROWS_GIVEN_UP:
            take = budget // ROW_TILE * ROW_TILE
        if take:
            takes[r.uid] = take
            budget -= take
    return takes


def _device_says_aligned(cu_q_lens, num_seqs, T):
    return bool(linear_attention.tile_layout(
        jnp.asarray(cu_q_lens, jnp.int32), jnp.asarray(num_seqs, jnp.int32),
        jnp.zeros((len(cu_q_lens) - 1,), jnp.int32), T, ROW_TILE)["aligned"])


def _check_tick(eng, takes, decode_rows):
    """Pack ``takes`` and hold every array to the requests' own state."""
    by_uid = {r.uid: r for r in eng.slots if r is not None}
    whole = sorted(u for u, t in takes.items() if t % ROW_TILE == 0)
    tails = sorted(u for u, t in takes.items() if t % ROW_TILE)
    assert list(takes) == whole + tails
    one_row = [r for r in eng.slots if r is not None
               and r.prefill_done >= r.ctx_len]
    assert len(one_row) == decode_rows
    order = [by_uid[u] for u in whole + tails] + one_row
    before = {r.uid: r.prefill_done for r in order}
    tables = [eng.page_table.copy()] + (
        [] if eng.kv.window is None else [eng.kv.window.page_table.copy()])
    stats = eng.host_stats
    rows0, tiles0 = stats.prompt_rows, stats.prompt_tiles

    arrays, sampled, sampling, t, _ = eng._pack_tick(takes)
    (token_ids, tok_src, positions, kv_lens, page_indices, cu_q_lens, num,
     new_kv_dest, sample_rows, sample_slot) = arrays[:10]
    if len(tables) == 1:
        page_indices, new_kv_dest = page_indices[None], new_kv_dest[None]
    S, page = eng.max_seqs, eng.page_size
    assert int(num[0]) == len(order) and not sampled
    assert t == sum(takes.values()) + decode_rows
    lens = [takes.get(r.uid, 1) for r in order]
    # whole-tile chunks from row 0, each on a tile of the tick; then the
    # tails; then the one-row sequences
    assert cu_q_lens.tolist() == np.concatenate(
        [[0], np.cumsum(lens), [t] * (S - len(order))]).tolist()
    for j in range(len(whole)):
        assert cu_q_lens[j] % ROW_TILE == 0
    assert all(n == 1 for n in lens[len(takes):])
    if eng.state_layers:
        assert arrays[10][:len(order)].tolist() == [r.slot for r in order]
    samplers = []
    for j, (r, n) in enumerate(zip(order, lens)):
        lo, hi = int(cu_q_lens[j]), int(cu_q_lens[j + 1])
        chunk = r.uid in takes
        p0 = before[r.uid] if chunk else r.length - 1
        pos = np.arange(p0, p0 + n)
        assert positions[0, lo:hi].tolist() == pos.tolist()
        assert kv_lens[j] == p0 + n
        assert sample_rows[j] == hi - 1
        if chunk:
            assert token_ids[0, lo:hi].tolist() == r.ctx[p0:p0 + n].tolist()
            assert r.prefill_done == p0 + n
        else:
            assert token_ids[0, lo] == 3 + r.slot and tok_src[0, lo] == -1
        ends = not chunk or r.prefill_done >= r.ctx_len
        assert sample_slot[j] == (r.slot if ends else -1)
        if ends:
            samplers.append((r.uid, j))
        for i, table in enumerate(tables):
            assert page_indices[i, j].tolist() == table[r.slot].tolist()
            assert new_kv_dest[i, lo:hi].tolist() == (
                table[r.slot, pos // page] * page + pos % page).tolist()
            assert (table[r.slot, pos // page] > 0).all()
    assert [(r.uid, j) for r, j in sampling] == samplers
    assert (new_kv_dest[:, t:] == 0).all() and (sample_slot[
        len(order):] == -1).all()
    # the plan counted rows and tiles, not the pack; the tiles a chunk's rows
    # fall in, row by row
    assert (stats.prompt_rows, stats.prompt_tiles) == (rows0, tiles0)
    # ... and said whether the chunks lie in the tick's own tiles, by the
    # rule the device evaluates from the packed ``cu_q_lens``
    assert _device_says_aligned(cu_q_lens, num, eng.T) == tiles_aligned(
        takes.values(), eng.T) == eng._plan_aligned
    return sum(len(set(np.arange(cu_q_lens[j], cu_q_lens[j + 1]) // ROW_TILE))
               for j in range(len(takes)))


CASES = [(seqs, chunk, d, what)
         for seqs, chunk in ((8, 1024), (32, 512))
         for d in (0, 1, 7, 24, 31, seqs) if d <= seqs
         for what in ("below", "at", "above", "cached", "tail_between")
         if d + (2 if what == "tail_between" else 1) <= seqs
         or (d == seqs and what == "below")]


@pytest.mark.parametrize("seqs,chunk,d,what", CASES)
def test_a_take_gives_up_a_few_rows_for_whole_tiles_and_lies_tiles_first(
        llama_params, seqs, chunk, d, what):
    eng = RaggedInferenceEngineV2(
        LlamaForCausalLM(CFG), params=llama_params, max_seqs=seqs,
        max_seq_len=5 * chunk + 128, prefill_chunk=chunk, page_size=64,
        num_pages=1 + seqs + 2 * (5 * chunk // 64 + 3))
    assert eng.T == seqs + chunk and eng.T in (1032, 544)
    prefilling = {
        "below": [(chunk - 24, 0)],
        "at": [(2 * chunk, chunk)],
        # a prefix of three pages already there: no tile boundary
        "cached": [(3 * chunk + 77, 192)],
        "above": [(4 * chunk + 300, chunk)],
        # the older prompt ends in this tick, the younger takes what whole
        # tiles are left and lies BEFORE it
        "tail_between": [(2 * chunk + 300, 2 * chunk), (3 * chunk, 0)],
    }[what] if d < seqs else []
    uids = _seat(eng, [9 + k for k in range(d)], prefilling)
    st = eng.host_stats
    want = _want_takes(eng, d)
    takes = eng._plan_rows()
    assert takes == want and not eng._page_limited
    few = (eng.T - d) % ROW_TILE <= ROWS_GIVEN_UP   # 8 - d of 1,032; 32 - d
    if what == "at" and d < seqs:
        assert takes == {uids[0]: chunk}        # its whole rest
    if what in ("above", "cached") and d < seqs:
        # a whole chunk whatever ``d`` where the rows given up are few; all
        # the tick's rows, as ever, where they are not (T = 544, d < 24)
        assert takes == {uids[0]: chunk if few else eng.T - d}
        assert few == (seqs == 8 or d >= 24)
    if what == "tail_between":
        left = eng.T - d - 300
        if left % ROW_TILE <= ROWS_GIVEN_UP:
            left -= left % ROW_TILE
        assert takes == {uids[1]: left, uids[0]: 300}
        # the older prompt's tail leads unless the younger's chunk is whole
        assert list(takes)[0] == (uids[1] if left % ROW_TILE == 0
                                  else uids[0])
    rows, tiles = st.prompt_rows, st.prompt_tiles
    assert rows == sum(takes.values())
    assert tiles == _check_tick(eng, takes, d)
    whole = [t for t in takes.values() if t % ROW_TILE == 0]
    if len(whole) == len(takes) and takes:
        assert rows == ROW_TILE * tiles
    eng.close()


def test_a_page_limited_take_is_whole_tiles_in_both_tables(trinity_params):
    """A window model whose own pool is short: the chunk is cut to the whole
    tiles the pool covers where that gives up a few rows, the tail is taken
    whole where it fits, and both tables' rows are the requests' own."""
    model = tr.TrinityForCausalLM(TRINITY)
    page = programs.paged_kv_page_bytes(model, TRINITY, 4, "none",
                                        window=True)
    eng = RaggedInferenceEngineV2(
        model, params=trinity_params, max_seqs=3, max_seq_len=1280,
        prefill_chunk=512, page_size=4, num_pages=700,
        window_pool_bytes=160 * page, decode_block_size=2)
    own = eng.kv.window
    assert own is not None and eng.T == 515 and own.bound == 133
    (long, short) = _seat(eng, [7], [(1200, 0), (40, 0)])
    # the pool as its neighbours' growth would leave it: the long prompt's
    # row cut back to 30 columns, every free page but three held elsewhere
    slot = next(r.slot for r in eng.slots if r is not None and r.uid == long)
    own.allocator.release_pages(
        slot, [int(p) for p in own.page_table[slot, 30:133]])
    own.page_table[slot, 30:] = -1
    own._top[slot] = 30
    held = [own.allocator.take_page()
            for _ in range(own.allocator.free_pages - 3)]
    takes = eng._plan_rows()
    # 512 rows asked, (30 + 3) pages of 4 coverable: one whole tile of the
    # 132; the younger prompt's forty rows are its whole rest and lie behind
    assert eng._page_limited and not eng._stalled
    assert list(takes.items()) == [(long, ROW_TILE), (short, 40)]
    assert eng.host_stats.prompt_tiles == _check_tick(eng, takes, 1) == 2
    for p in held:
        own.allocator.decref(p)
    eng.audit_kv_sharing()
    eng.close()


def test_a_per_slot_state_model_finds_its_slots_in_the_packed_order():
    """``seq_slot [max_seqs]`` rides only with per-slot state: the younger
    prompt's whole tile first, the older one's tail, the decode rows."""
    cfg = lfm2_moe.get_config("tinylfm2", dtype=jnp.float32,
                              param_dtype=jnp.float32, remat=False,
                              use_flash_attention=False)
    model = lfm2_moe.Lfm2MoeForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(7),
                                 np.zeros((1, 8), np.int32))["params"]
    eng = RaggedInferenceEngineV2(
        model, params=params, max_seqs=4, max_seq_len=704, prefill_chunk=256,
        page_size=8, num_pages=200, decode_block_size=2)
    assert eng.state_layers
    tail, whole = _seat(eng, [9, 11], [(380, 256), (600, 0)])
    takes = eng._plan_rows()
    # 260 rows less two decode rows and the tail's 124: 134, six given up
    assert list(takes.items()) == [(whole, ROW_TILE), (tail, 124)]
    assert _check_tick(eng, takes, 2) == 2
    eng.close()


# -- the tick's own tiles ------------------------------------------------------

@pytest.mark.parametrize("takes,T,want", [
    ([1024], 1032, True),               # one chunk from row 0
    ([512, 512], 1032, True),           # two, each on a tile
    ([384, 300], 1032, True),           # a tail behind whole tiles
    ([300], 1032, True),                # a prompt's last chunk alone
    ([], 1032, True),                   # decode rows only
    ([1, 1], 1032, True),               # one-row chunks are no tiles' matter
    ([300, 724], 1032, False),          # the head of the next behind a tail
    ([128, 1, 128], 1032, False),       # a row between two chunks
    ([1, 128], 1032, False),
    ([1028], 1032, False),              # the ninth tile is not whole
    ([1024], 1024, True),
    ([16], 20, False),                  # a tick under a tile has none
    ([], 20, True),
])
def test_the_hosts_rule_of_a_tick_in_its_own_tiles(takes, T, want):
    assert tiles_aligned(takes, T) is want
    cu = np.concatenate([[0], np.cumsum(takes + [1] * (8 - len(takes)))])
    assert _device_says_aligned(cu, [8], T) is want


@pytest.mark.parametrize("seed", range(6))
def test_the_hosts_rule_is_the_devices_scalar_over_random_ticks(seed):
    """Chunks as ``_pack_tick`` lays them (the multi-row ones in ANY order
    here, one-row chunks among them, the decode rows behind): the count on
    the host and the scalar on the device are one rule."""
    r = np.random.default_rng(seed)
    said = []
    for _ in range(40):
        T = int(r.choice([520, 1032, 1024, 264]))
        takes, room = [], T - 8
        while room > 0 and len(takes) < 6 and r.random() < 0.8:
            n = int(r.choice([1, ROW_TILE, 2 * ROW_TILE, 3 * ROW_TILE,
                              int(r.integers(2, 300))]))
            n = min(n, room)
            takes.append(n)
            room -= n
        seqs = len(takes) + int(r.integers(0, 9 - len(takes)))
        lens = takes + [1] * (seqs - len(takes))
        cu = np.concatenate([[0], np.cumsum(lens + [0] * (8 - seqs))])
        said.append(tiles_aligned(takes, T))
        assert _device_says_aligned(cu, [seqs], T) is said[-1], (takes, T)
    assert 5 < sum(said) < 35


def test_a_tick_of_solars_tiny_model_counts_into_the_aligned_share(
        monkeypatch):
    """Two prompts through a tick of 128 + 4 rows: the chunks of the first
    lie on the tick's tiles, the tick that holds its tail beside the
    second's head does not, and the engine serves the same tokens whichever
    layout the KDA layers' scan reads (``scan_tick``'s two branches)."""
    from deepspeed_tpu.models import solar_open2

    cfg = solar_open2.get_config("tinysolar", dtype=jnp.float32,
                                 param_dtype=jnp.float32, remat=False,
                                 use_flash_attention=False)
    model = solar_open2.SolarOpen2ForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(3),
                                 np.zeros((1, 8), np.int32))["params"]
    prompts = [_tokens(300, 11), _tokens(200, 12)]

    def serve():
        eng = RaggedInferenceEngineV2(
            model, params=params, max_seqs=4, max_seq_len=384,
            prefill_chunk=ROW_TILE, page_size=16, num_pages=60,
            decode_block_size=4)
        assert eng.T == ROW_TILE + 4
        flags = []
        plan = eng._plan_rows

        def watching():
            takes = plan()
            flags.append((list((takes or {}).values()), eng._plan_aligned))
            return takes

        eng._plan_rows = watching
        try:
            out = eng.generate_all(prompts, max_new_tokens=5)
            return ({u: t.tolist() for u, t in out.items()}, flags,
                    eng.serving_stages()["fused_tick_host"])
        finally:
            eng.close()

    tokens, flags, got = serve()
    chunks = [f for f in flags if f[0]]
    assert chunks == [([128], True), ([128], True), ([44, 88], False),
                      ([112], True)]
    assert got["aligned_ticks"] == got["ticks"] - 1
    assert got["aligned_tick_share"] == round(
        got["aligned_ticks"] / got["ticks"], 4)
    # the same run with the device told that no tick is aligned
    layout = linear_attention.tile_layout
    monkeypatch.setattr(
        linear_attention, "tile_layout",
        lambda *a: {**layout(*a), "aligned": jnp.asarray(False)})
    gathered, flags_2, _ = serve()
    assert flags_2 == flags and gathered == tokens


# -- a served run -------------------------------------------------------------

def test_three_ticks_of_a_prompt_beside_a_decode_row_serve_the_reference(
        llama_params):
    v1 = deepspeed_tpu.init_inference(
        model=LlamaForCausalLM(CFG), params=llama_params, max_out_tokens=512,
        dtype="float32")
    solo = lambda p, n: np.asarray(v1.generate(  # noqa: E731
        p[None], max_new_tokens=n, do_sample=False))[0]
    eng = RaggedInferenceEngineV2(
        LlamaForCausalLM(CFG), params=llama_params, max_seqs=4,
        max_seq_len=512, prefill_chunk=ROW_TILE)
    short, long = _tokens(5, 1), _tokens(300, 2)
    first = eng.put_request(short, max_new_tokens=40)
    for _ in range(2):
        eng.step()
    second = eng.put_request(long, max_new_tokens=6)
    seen = []
    pack = eng._pack_tick

    def watching(takes):
        out = pack(takes)
        seen.append((dict(takes), np.diff(out[0][5]).tolist()))
        return out

    eng._pack_tick = watching
    while eng.has_work():
        eng.step()
    outs = dict(eng.get_outputs())
    np.testing.assert_array_equal(outs[first], solo(short, 40))
    np.testing.assert_array_equal(outs[second], solo(long, 6))
    # 128 + 128 + 44, each before the short request's one decode row
    assert [t for t, _ in seen] == [{second: 128}, {second: 128},
                                    {second: 44}]
    assert [q[:2] for _, q in seen] == [[128, 1], [128, 1], [44, 1]]
    got = eng.serving_stages()["fused_tick_host"]
    assert got["prompt_rows"] == 305 and got["prompt_tiles"] == 4
    assert got["prompt_rows_per_tile"] == 76.25
    assert got["ticks"] == eng.host_stats.fused_ticks == 4
    eng.close()
