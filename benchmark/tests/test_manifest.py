"""A lint of ``BENCHMARK.json`` against the rules a reader can check
without the chip, and the proof that a configuration, a mix and a
per-layer metric are added as files of their own."""
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap
import types

from benchmark import harness as H

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|head_dim|"
                   r"num_experts_per_tok")


def test_manifest_shape_names_and_units():
    m = H.manifest()
    assert set(m) == KEYS
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in m[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    for met in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(met["unit"]), met
        assert met["better"] in ("lower", "higher")
        assert met["source"] in ("device_trace", "program_span",
                                 "program_counter", "host_clock")
    for met in m["end_to_end"]:
        assert set(met) <= {"name", "unit", "better", "bound", "source",
                            "workloads"}
        assert met["source"] in ("host_clock", "device_trace")
        assert 0.01 <= met["bound"] <= 0.1
    assert any(e["name"] == "setup_s" and "workloads" not in e
               for e in m["end_to_end"])
    for met in m["per_layer"]:
        assert set(met) <= {"name", "unit", "better", "source", "layer",
                            "moves", "workloads"}
        assert 1 <= len(met["layer"]) <= 200 and "\n" not in met["layer"]
        if "roofline" in met["name"] or "mfu" in met["name"]:
            assert met["unit"] == "%"


def test_cells_files_and_chips():
    m = H.manifest()
    used = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        cell = H.Cell(w["name"])            # both files exist and parse
        assert cell.config["chips"] == w["chips"]
        assert cell.config["kind"] in ("serve", "train")
        used.add(w["config"])
    assert used == {c["name"] for c in m["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        body = H.load_json(os.path.join(H.ROOT, c["file"]))
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
        for key in ("source", "reduced", "assumed", "deployment", "chips"):
            assert key in body, (c["name"], key)


def test_every_metric_is_reported_where_its_end_to_end_metric_is():
    m = H.manifest()
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for w in m["workloads"]:
        cell = H.Cell(w["name"])
        mine = {e["name"] for e in cell.metrics("end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layer = cell.metrics("per_layer")
        assert layer
        for met in layer:
            assert met["moves"] in e2e and met["moves"] in mine, met
            reader = H.reader_of(met["name"])
            assert os.path.exists(os.path.join(
                H.HERE, "layer_metrics", reader + ".py")), met
        gen = cell.traffic["generator"]
        assert os.path.exists(os.path.join(H.HERE, "generators",
                                           gen + ".py"))


def test_per_layer_is_one_entry_a_reader_and_moves_with_its_cells():
    """``per_layer`` may hold 128 entries, so an entry is a (reader,
    ``moves``) pair with the LIST of its cells: bare where it has several,
    ``<reader>.<suffix>`` where it has one.  No cell is named twice for one
    pair (it would report one reading under two names), every cell named
    exists and reports the end-to-end metric the entry moves, and the
    cells stand in the order of ``workloads``."""
    m = H.manifest()
    order = [w["name"] for w in m["workloads"]]
    e2e = {e["name"]: e.get("workloads", order) for e in m["end_to_end"]}
    seen = set()
    for met in m["per_layer"]:
        cells = met["workloads"]
        assert cells and len(cells) == len(set(cells)), met
        assert cells == sorted(cells, key=order.index), met
        assert ("." in met["name"]) == (len(cells) == 1), met
        for c in cells:
            assert c in e2e[met["moves"]], met
            key = (H.reader_of(met["name"]), met["moves"], c)
            assert key not in seen, met
            seen.add(key)


def test_the_file_keeps_under_its_two_ceilings():
    """The HARD ceiling, of the file as it stands: 128 ``per_layer`` entries
    and 65,536 bytes.  The ROOM a fold must leave is another statement,
    about the folded cells alone, and the rule that joins the two stands
    there (``test_a_fifteenth_cell_of_twenty_four_entries_fits``)."""
    assert len(H.manifest()["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(H.ROOT, "BENCHMARK.json")) < 65536


# what each cell of the fourth fold reports, in the order of ``workloads``
FOLDED = {"chat-open": 18, "pretrain-zero3": 11, "longdoc-closed": 15,
          "reasoning-closed": 13, "olmoe-reasoning-closed": 15,
          "ouro-reasoning-closed": 15, "lfm2-reasoning-closed": 21,
          "glm-longdoc16k-closed": 23, "sala-longdoc16k-closed": 21,
          "keye-longdoc32k-closed": 23, "olmo-hybrid-longdoc16k-closed": 19,
          "solar-longdoc16k-closed": 24, "trinity-longdoc32k-closed": 25,
          "smallthinker-reasoning8k-closed": 19}
# what a fold must leave free beside the folded cells' entries alone
ROOM = 24


def _folded_alone(m):
    """``m`` without the cells a later PR added beside the folded ones:
    their workloads, their configurations, their names on the end-to-end
    lists and every ``per_layer`` entry that names one of them."""
    m["per_layer"] = [e for e in m["per_layer"]
                      if set(e["workloads"]) <= set(FOLDED)]
    m["workloads"] = [w for w in m["workloads"] if w["name"] in FOLDED]
    used = {w["config"] for w in m["workloads"]}
    m["configs"] = [c for c in m["configs"] if c["name"] in used]
    for e in m["end_to_end"]:
        if "workloads" in e:
            e["workloads"] = [c for c in e["workloads"] if c in FOLDED]
    return m


def test_the_fourth_fold_left_95_entries_and_lost_none():
    """The fourteen cells the benchmark had when ``per_layer`` was folded a
    fourth time take 95 entries between them, ONE for each pair of reader
    and ``moves``, and each reports the count in ``FOLDED`` (262 readings in
    all: the 259 before the fold, every one under its reader, and the
    three idle shares PR 64 had no entry left for in the SmallThinker cell.
    The Solar and Trinity cells stay off ``bias_swap_pct``'s list, because
    both configurations start their selection bias at zero and the share
    reads 0 on every seed).  Entries of a cell a later ``model_config`` PR adds
    stand beside these and are not counted here; the next fold takes them
    into the lists and restates this table."""
    m = H.manifest()
    assert [w["name"] for w in m["workloads"]][:len(FOLDED)] == list(FOLDED)
    mine = _folded_alone(H.manifest())["per_layer"]
    assert len(mine) == 95
    pairs = [(H.reader_of(e["name"]), e["moves"]) for e in mine]
    assert len(pairs) == len(set(pairs))
    for cell, count in FOLDED.items():
        assert len(H.Cell(cell).metrics("per_layer")) == count, cell
    assert sum(FOLDED.values()) == sum(len(e["workloads"]) for e in mine) \
        == 262


def test_a_fold_leaves_room_for_a_cell_in_the_file_as_it_stands():
    """While no cell stands outside ``FOLDED`` the file AS IT STANDS has
    ``ROOM`` entries free: a fold that leaves less fails here.  A
    ``model_config`` PR that uses the room adds a cell outside the table,
    and from then on the file is held by its two ceilings alone."""
    m = H.manifest()
    if {w["name"] for w in m["workloads"]} <= set(FOLDED):
        assert 128 - len(m["per_layer"]) >= ROOM
    assert 128 - len(_folded_alone(m)["per_layer"]) >= ROOM


# the benchmark's layers, and each reader's `unit`, `better`, `source` and
# layer (an index into LAYERS) as the parent of the fourth fold had them:
# all four are the READER's in every entry, and `moves` is the cell's
LAYERS = (
    "load generator (benchmark/loadgen.py)",
    "entry points and router (serving/server.py, serving/router.py)",
    "serving engine host loop (inference/v2/ragged_engine.py)",
    "model step (jitted programs)",
    "device",
    "training engine (runtime/engine.py)",
    "ZeRO plan as collectives (runtime/zero, comm)",
    "kernels, as the step they dominate",
    "expert layer (moe/sharded_moe.py, moe/layer.py)",
)
READERS_AT_PR_67 = """
gen_late_p95_ms ms lower host_clock 0
queue_wait_p95_ms ms lower program_span 1
ttft_p95_ms ms lower host_clock 1
ttft_p50_ms ms lower host_clock 1
host_ms_per_dispatch ms lower program_counter 2
prefill_p50_ms ms lower program_span 2
decode_step_device_ms ms lower device_trace 3
device_idle_pct % lower device_trace 4
step_device_ms ms lower device_trace 5
collective_exposed_pct % lower device_trace 6
step_roofline % higher device_trace 7
idle_plan_pct % lower program_span 2
idle_feed_pct % lower program_span 2
idle_sample_pct % lower program_span 2
idle_between_ops_pct % lower program_span 2
idle_unnamed_pct % lower program_span 2
engine_step_host_ms ms lower program_span 2
prefill_roofline_counted % higher program_counter 7
decode_roofline_counted % higher program_counter 7
moe_decode_roofline % higher program_counter 7
moe_expert_roofline % higher device_trace 8
moe_load_max_over_mean ratio lower program_counter 8
loop_decode_step_ms ms lower device_trace 3
loop_decode_roofline % higher program_counter 7
kv_pool_peak_pct % higher program_counter 2
loop_steps_per_token passes lower program_counter 3
hybrid_decode_roofline % higher program_counter 7
hybrid_expert_roofline % higher device_trace 8
hybrid_expert_share_pct % higher device_trace 8
hybrid_load_max_over_mean ratio lower program_counter 8
bias_swap_pct % higher program_counter 8
state_rows_per_tick rows higher program_counter 2
scope_attn_pct % higher device_trace 3
scope_mlp_pct % higher device_trace 3
scope_moe_pct % higher device_trace 8
scope_conv_pct % higher device_trace 3
scope_head_pct % lower device_trace 3
device_unscoped_pct % lower device_trace 4
scope_remat_pct % lower device_trace 5
scope_bwd_pct % higher device_trace 5
scope_optim_pct % lower device_trace 5
mla_prefill_roofline % higher device_trace 7
mla_decode_roofline % higher device_trace 7
mla_share_pct % higher device_trace 3
latent_bytes_per_token bytes lower program_counter 2
scope_linear_pct % higher device_trace 3
linear_scan_roofline % higher device_trace 7
linear_decode_roofline % higher device_trace 7
sparse_prefill_roofline % higher device_trace 7
sparse_decode_roofline % higher device_trace 7
sparse_selected_share_pct % lower program_counter 2
state_bytes_per_seq bytes lower program_counter 2
index_prefill_roofline % higher device_trace 7
index_decode_roofline % higher device_trace 7
tokensel_prefill_roofline % higher device_trace 7
tokensel_decode_roofline % higher device_trace 7
tokensel_selected_share_pct % lower program_counter 2
tokensel_share_pct % higher device_trace 3
index_bytes_per_token bytes lower program_counter 2
delta_scan_roofline % higher device_trace 7
delta_decode_roofline % higher device_trace 7
delta_share_pct % higher device_trace 3
fused_tick_host_ms ms lower program_span 2
kda_scan_roofline % higher device_trace 7
kda_decode_roofline % higher device_trace 7
kda_share_pct % higher device_trace 3
expert_rows_held_share_pct % higher program_counter 8
window_pool_peak_pct % higher program_counter 2
window_pages_saved_pct % higher program_counter 2
attn_window_share_pct % higher device_trace 3
attn_full_share_pct % higher device_trace 3
mixed_prefill_roofline % higher device_trace 7
mixed_decode_roofline % higher device_trace 7
reglu_expert_roofline % higher device_trace 8
gqa4_decode_roofline % higher device_trace 7
gqa4_prefill_roofline % higher device_trace 7
"""
# the readers each cell reported at the parent of the fourth fold
CELLS_AT_PR_67 = {
    "chat-open":
        "gen_late_p95_ms queue_wait_p95_ms ttft_p95_ms ttft_p50_ms "
        "host_ms_per_dispatch decode_step_device_ms device_idle_pct "
        "idle_plan_pct idle_feed_pct idle_sample_pct idle_between_ops_pct "
        "idle_unnamed_pct engine_step_host_ms decode_roofline_counted "
        "scope_attn_pct scope_mlp_pct scope_head_pct device_unscoped_pct",
    "pretrain-zero3":
        "device_idle_pct step_device_ms collective_exposed_pct "
        "step_roofline scope_attn_pct scope_mlp_pct scope_head_pct "
        "device_unscoped_pct scope_remat_pct scope_bwd_pct scope_optim_pct",
    "longdoc-closed":
        "ttft_p50_ms prefill_p50_ms device_idle_pct idle_plan_pct "
        "idle_feed_pct idle_sample_pct idle_between_ops_pct "
        "idle_unnamed_pct engine_step_host_ms prefill_roofline_counted "
        "scope_attn_pct scope_mlp_pct scope_head_pct device_unscoped_pct "
        "fused_tick_host_ms",
    "reasoning-closed":
        "device_idle_pct idle_plan_pct idle_feed_pct idle_sample_pct "
        "idle_between_ops_pct idle_unnamed_pct engine_step_host_ms "
        "decode_step_device_ms decode_roofline_counted scope_attn_pct "
        "scope_mlp_pct scope_head_pct device_unscoped_pct",
    "olmoe-reasoning-closed":
        "device_idle_pct idle_plan_pct idle_feed_pct idle_sample_pct "
        "idle_between_ops_pct idle_unnamed_pct engine_step_host_ms "
        "decode_step_device_ms moe_decode_roofline moe_expert_roofline "
        "moe_load_max_over_mean scope_attn_pct scope_moe_pct scope_head_pct "
        "device_unscoped_pct",
    "ouro-reasoning-closed":
        "device_idle_pct idle_plan_pct idle_feed_pct idle_sample_pct "
        "idle_between_ops_pct idle_unnamed_pct engine_step_host_ms "
        "loop_decode_step_ms loop_decode_roofline kv_pool_peak_pct "
        "loop_steps_per_token scope_attn_pct scope_mlp_pct scope_head_pct "
        "device_unscoped_pct",
    "lfm2-reasoning-closed":
        "device_idle_pct idle_plan_pct idle_feed_pct idle_sample_pct "
        "idle_between_ops_pct idle_unnamed_pct engine_step_host_ms "
        "decode_step_device_ms kv_pool_peak_pct hybrid_decode_roofline "
        "hybrid_expert_roofline hybrid_expert_share_pct "
        "hybrid_load_max_over_mean bias_swap_pct state_rows_per_tick "
        "scope_attn_pct scope_mlp_pct scope_moe_pct scope_conv_pct "
        "scope_head_pct device_unscoped_pct",
    "glm-longdoc16k-closed":
        "ttft_p50_ms prefill_p50_ms device_idle_pct idle_plan_pct "
        "idle_feed_pct idle_sample_pct idle_between_ops_pct "
        "idle_unnamed_pct engine_step_host_ms kv_pool_peak_pct "
        "hybrid_expert_roofline hybrid_expert_share_pct "
        "hybrid_load_max_over_mean bias_swap_pct scope_attn_pct "
        "scope_mlp_pct scope_moe_pct scope_head_pct device_unscoped_pct "
        "mla_prefill_roofline mla_decode_roofline mla_share_pct "
        "latent_bytes_per_token",
    "sala-longdoc16k-closed":
        "ttft_p50_ms prefill_p50_ms device_idle_pct idle_plan_pct "
        "idle_feed_pct idle_sample_pct idle_between_ops_pct "
        "idle_unnamed_pct engine_step_host_ms kv_pool_peak_pct "
        "scope_attn_pct scope_mlp_pct scope_head_pct device_unscoped_pct "
        "scope_linear_pct linear_scan_roofline linear_decode_roofline "
        "sparse_prefill_roofline sparse_decode_roofline "
        "sparse_selected_share_pct state_bytes_per_seq",
    "keye-longdoc32k-closed":
        "ttft_p50_ms prefill_p50_ms device_idle_pct idle_plan_pct "
        "idle_feed_pct idle_sample_pct idle_between_ops_pct "
        "idle_unnamed_pct engine_step_host_ms moe_load_max_over_mean "
        "kv_pool_peak_pct hybrid_expert_roofline scope_attn_pct "
        "scope_moe_pct scope_head_pct device_unscoped_pct "
        "index_prefill_roofline index_decode_roofline "
        "tokensel_prefill_roofline tokensel_decode_roofline "
        "tokensel_selected_share_pct tokensel_share_pct "
        "index_bytes_per_token",
    "olmo-hybrid-longdoc16k-closed":
        "ttft_p50_ms prefill_p50_ms device_idle_pct idle_plan_pct "
        "idle_feed_pct idle_sample_pct idle_between_ops_pct "
        "idle_unnamed_pct engine_step_host_ms kv_pool_peak_pct "
        "scope_attn_pct scope_mlp_pct scope_head_pct device_unscoped_pct "
        "scope_linear_pct state_bytes_per_seq delta_scan_roofline "
        "delta_decode_roofline delta_share_pct",
    "solar-longdoc16k-closed":
        "ttft_p50_ms prefill_p50_ms device_idle_pct idle_plan_pct "
        "idle_feed_pct idle_sample_pct idle_between_ops_pct "
        "idle_unnamed_pct engine_step_host_ms kv_pool_peak_pct "
        "hybrid_expert_roofline hybrid_expert_share_pct "
        "hybrid_load_max_over_mean scope_attn_pct scope_mlp_pct "
        "scope_moe_pct scope_head_pct device_unscoped_pct scope_linear_pct "
        "state_bytes_per_seq kda_scan_roofline kda_decode_roofline "
        "kda_share_pct expert_rows_held_share_pct",
    "trinity-longdoc32k-closed":
        "ttft_p50_ms prefill_p50_ms device_idle_pct idle_plan_pct "
        "idle_feed_pct idle_sample_pct idle_unnamed_pct "
        "idle_between_ops_pct engine_step_host_ms kv_pool_peak_pct "
        "scope_attn_pct scope_mlp_pct scope_moe_pct scope_head_pct "
        "device_unscoped_pct hybrid_expert_roofline hybrid_expert_share_pct "
        "hybrid_load_max_over_mean expert_rows_held_share_pct "
        "window_pool_peak_pct window_pages_saved_pct attn_window_share_pct "
        "attn_full_share_pct mixed_prefill_roofline mixed_decode_roofline",
    "smallthinker-reasoning8k-closed":
        "device_idle_pct idle_plan_pct idle_feed_pct engine_step_host_ms "
        "decode_step_device_ms kv_pool_peak_pct window_pool_peak_pct "
        "window_pages_saved_pct scope_attn_pct scope_moe_pct scope_head_pct "
        "device_unscoped_pct attn_window_share_pct reglu_expert_roofline "
        "gqa4_decode_roofline gqa4_prefill_roofline",
}


def test_every_cell_reads_what_it_read_before_the_fourth_fold():
    """A fold renames and retires nothing: every cell names the READERS it
    named at the parent (PR 67), each with the parent's ``unit``,
    ``better``, ``source`` and ``layer``, moving the end-to-end metric the
    cell's entries moved there; the SmallThinker cell names the three idle
    shares beside them, so that its five partition its ``device_idle_pct``
    as every serving cell's do (``test_host_spans.py``)."""
    fields = {}
    for row in READERS_AT_PR_67.split("\n")[1:-1]:
        reader, unit, better, source, layer = row.split()
        fields[reader] = dict(unit=unit, better=better, source=source,
                              layer=LAYERS[int(layer)])
    moves = {"chat-open": "tpot_p95_ms", "pretrain-zero3":
             "train_tokens_per_s"}
    new = {"smallthinker-reasoning8k-closed": [
        "idle_sample_pct", "idle_between_ops_pct", "idle_unnamed_pct"]}
    assert set(CELLS_AT_PR_67) == set(FOLDED)
    for cell, readers in CELLS_AT_PR_67.items():
        had = readers.split() + new.get(cell, [])
        got = {H.reader_of(e["name"]): e
               for e in H.Cell(cell).metrics("per_layer")}
        assert sorted(got) == sorted(had), cell
        for reader, e in got.items():
            want = dict(fields[reader],
                        moves=moves.get(cell, "total_tokens_per_s"))
            assert {k: e[k] for k in want} == want, (cell, reader)


def test_no_reader_file_is_left_that_nothing_uses():
    """A file of ``layer_metrics/`` is a reader some entry names, or a
    helper some other file there (or a driver) imports."""
    m = H.manifest()
    named = {H.reader_of(e["name"]) for e in m["per_layer"]}
    folder = os.path.join(H.HERE, "layer_metrics")
    files = {f[:-3] for f in os.listdir(folder) if f.endswith(".py")}
    text = ""
    for base in (folder, os.path.join(H.HERE, "drivers"), H.HERE):
        for f in os.listdir(base):
            if f.endswith(".py"):
                text += open(os.path.join(base, f)).read()
    for f in sorted(files - named):
        assert re.search(r"import .*\b%s\b" % f, text), f


def _add_cell(m, name, like, entries, **fields):
    """A cell ``name`` shaped like the workload ``like``, on the list of
    ``total_tokens_per_s``, with per-layer ``entries`` of its own."""
    m["workloads"].append(dict(like, name=name, **fields))
    for e in m["end_to_end"]:
        if e["name"] == "total_tokens_per_s":
            e["workloads"].append(name)
    m["per_layer"] += entries


def test_a_ninth_cell_adds_suffixed_entries_beside_the_folded_ones(
        monkeypatch):
    """How the next ``model_config`` PR lists its cell without editing a
    list: one ``<reader>.<suffix>`` entry a reader, beside the folded
    entry.  The new cell reports its own and none of the others'; no other
    cell reports the new one; every reading keeps its reader."""
    m = H.manifest()
    folded = next(e for e in m["per_layer"] if e["name"] == "device_idle_pct")
    before = {w["name"]: [e["name"] for e in H.Cell(w["name"]).metrics(
        "per_layer")] for w in m["workloads"]}
    mine = [dict(folded, name=f"{r}.ninth", workloads=["ninth"])
            for r in ("device_idle_pct", "scope_attn_pct")]
    _add_cell(m, "ninth", m["workloads"][2], mine, traffic="reasoning-closed")
    monkeypatch.setattr(H, "manifest", lambda: m)
    assert [e["name"] for e in H.Cell("ninth").metrics("per_layer")] == \
        ["device_idle_pct.ninth", "scope_attn_pct.ninth"]
    for name, had in before.items():
        assert [e["name"] for e in H.Cell(name).metrics("per_layer")] == had
    # the harness finds the reader by the part before the first "."
    monkeypatch.setattr(H, "plugin", lambda group, name:
                        types.SimpleNamespace(read=lambda ctx: len(name)))
    assert H.layer_metrics(H.Cell("ninth"), {}) == {
        "device_idle_pct.ninth": {"value": 15.0, "unit": "%"},
        "scope_attn_pct.ninth": {"value": 14.0, "unit": "%"}}


def test_a_fifteenth_cell_of_twenty_four_entries_fits(monkeypatch):
    """The ROOM the fourth fold left, at the size the last four
    configurations had (19, 24, 25 and 19 entries): beside THE FOLDED
    CELLS' ENTRIES ALONE, a configuration, a cell and TWENTY-FOUR
    ``<reader>.<suffix>`` entries, sixteen on readers the benchmark shares
    and eight on readers of the cell's own, fit under 128 entries and
    under the 65,536 bytes the file may have; the cell reports its 24 and
    every folded cell what it reported.  Every cell outside ``FOLDED`` is
    dropped from the copy first, so this goes on saying "the last fold
    left room for 24" after a ``model_config`` PR has used that room: such
    a PR may use every free entry up to 128, which
    ``test_the_file_keeps_under_its_two_ceilings`` holds of the file as it
    stands, and a ``benchmark`` PR folds when fewer than 24 are free."""
    m = _folded_alone(H.manifest())
    monkeypatch.setattr(H, "manifest", lambda: m)
    before = {w["name"]: [e["name"] for e in H.Cell(w["name"]).metrics(
        "per_layer")] for w in m["workloads"]}
    assert {k: len(v) for k, v in before.items()} == FOLDED
    # the first sixteen shared readers the widest configuration is on
    shared = [e for e in H.Cell("trinity-longdoc32k-closed").metrics(
        "per_layer") if "." not in e["name"]][:16]
    widest = max(m["per_layer"], key=lambda e: len(e["layer"]))
    mine = [dict(e, name=e["name"] + ".fifteenth", workloads=["fifteenth"])
            for e in shared]
    mine += [dict(widest, name=f"a_scan_of_its_own_{i}_roofline.fifteenth",
                  unit="%", workloads=["fifteenth"]) for i in range(8)]
    assert len(mine) == ROOM and len({e["name"] for e in mine}) == ROOM
    assert all(NAME.match(e["name"]) for e in mine)
    longest = max(m["configs"], key=lambda c: len(json.dumps(c)))
    m["configs"].append(dict(longest, name="fifteenth-serve"))
    _add_cell(m, "fifteenth",
              max(m["workloads"], key=lambda w: len(w["why"])),
              mine, config="fifteenth-serve", traffic="longdoc32k-closed")
    assert len(m["per_layer"]) <= 128
    assert len(json.dumps(m, indent=1).encode()) < 65536
    cell = H.Cell("fifteenth")
    assert [e["name"] for e in cell.metrics("per_layer")] == \
        [e["name"] for e in mine]
    for name, had in before.items():
        assert [e["name"] for e in H.Cell(name).metrics("per_layer")] == had
    monkeypatch.setattr(H, "plugin", lambda group, name:
                        types.SimpleNamespace(read=lambda ctx: 1.5))
    got = H.layer_metrics(cell, {})
    assert list(got) == [e["name"] for e in mine]
    assert got["hybrid_load_max_over_mean.fifteenth"] == {"value": 1.5,
                                                          "unit": "ratio"}


def test_nothing_in_the_benchmark_imports_bench_or_private_engine_names():
    bad = re.compile(r"^\s*(import|from)\s+(bench|chip_smoke)\b|"
                     r"engine\._[a-z]|eng\._[a-z]|deepspeed_tpu\S*\s+import\s+_[a-z]", re.M)
    for base, _, files in os.walk(H.HERE):
        if os.sep + "out" in base or "__pycache__" in base:
            continue
        for f in files:
            if f.endswith(".py") and f != "test_manifest.py":
                text = open(os.path.join(base, f)).read()
                assert not bad.search(text), os.path.join(base, f)


def test_a_configuration_a_mix_and_a_metric_are_added_as_files(tmp_path):
    """A throw-away copy of the benchmark gains a configuration, a mix and
    a per-layer metric -- three new files and three manifest entries, no
    existing file edited -- and the tiny CPU driver runs the new cell."""
    root = tmp_path / "copy"
    shutil.copytree(H.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    m = H.manifest()
    cfg = H.load_json(os.path.join(H.HERE, "configs",
                                   "mistral-7b-serve.json"))
    cfg["deployment"] = "throw-away"
    (root / "benchmark/configs/extra-serve.json").write_text(json.dumps(cfg))
    mix = H.load_json(os.path.join(H.HERE, "traffic", "longdoc-closed.json"))
    mix["clients"] = 2
    (root / "benchmark/traffic/extra-closed.json").write_text(json.dumps(mix))
    (root / "benchmark/layer_metrics/requests_done.py").write_text(
        "def read(ctx):\n    return float(len(ctx['sample']))\n")
    m["configs"].append({"name": "extra-serve", "source": cfg["source"],
                         "file": "benchmark/configs/extra-serve.json",
                         "reduced": cfg["reduced"], "why": "lint"})
    m["workloads"].append({"name": "extra", "config": "extra-serve",
                           "traffic": "extra-closed", "chips": 1,
                           "why": "lint"})
    for e in m["end_to_end"]:
        if e["name"] == "total_tokens_per_s":
            e["workloads"].append("extra")
    m["per_layer"].append({"name": "requests_done.extra", "unit": "requests",
                           "better": "higher", "source": "program_counter",
                           "layer": "load generator", "workloads": ["extra"],
                           "moves": "total_tokens_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    script = textwrap.dedent("""
        import json, sys, time
        T = time.monotonic()
        from benchmark import harness as H
        from benchmark.drivers import serve
        from benchmark.tests import tiny
        assert H.ROOT == sys.argv[1], (H.ROOT, sys.argv[1])
        cell = tiny.serve_cell("extra")
        ctx = {"cell": cell, "sample": [1, 2, 3]}
        got = H.layer_metrics(cell, ctx)
        assert got == {"requests_done.extra":
                       {"value": 3.0, "unit": "requests"}}, got
        line = json.loads(serve.run(cell, 5, 2.0, False, T, allow_cpu=True))
        assert set(line["metrics"]) == {"total_tokens_per_s", "setup_s"}
        assert line["correct"] and line["attempted"] > 0, line
        print("ok")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), H.ROOT]))
    out = subprocess.run([sys.executable, "-c", script, str(root)], env=env,
                         cwd=str(root), capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("ok")
