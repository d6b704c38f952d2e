"""The ``scope_*_pct`` readers and ``device_unscoped_pct`` on a hand-built
trace and hand-written compiled texts: the join by program and result name,
the partition into families and the log line's rest, a program the registry
does not know, an empty registry, and which cells list each reader."""
import json
import types

import pytest

from benchmark import harness as H
from benchmark import trace_reduce as TR
from benchmark.layer_metrics import (device_unscoped_pct, scope_attn_pct,
                                     scope_bwd_pct, scope_conv_pct,
                                     scope_head_pct, scope_mlp_pct,
                                     scope_moe_pct, scope_optim_pct,
                                     scope_remat_pct, scope_trace)

MS = 1e6
FAMILIES = {"scope_attn_pct": scope_attn_pct, "scope_mlp_pct": scope_mlp_pct,
            "scope_moe_pct": scope_moe_pct, "scope_conv_pct": scope_conv_pct,
            "scope_head_pct": scope_head_pct,
            "device_unscoped_pct": device_unscoped_pct,
            "scope_remat_pct": scope_remat_pct,
            "scope_bwd_pct": scope_bwd_pct,
            "scope_optim_pct": scope_optim_pct}


def _instr(name, opcode, shape, operands, path=None):
    meta = (f', metadata={{op_name="jit(p)/{path}" stack_frame_id=1}}'
            if path else "")
    args = ", ".join("%" + o for o in operands)
    return f"  %{name} = {shape}{{1,0}} {opcode}({args}){meta}"


# the decode block: a layer of attention and a dense MLP whose weight comes
# in slices, a conv layer with an expert layer, the head, the sampling
DECODE = "\n".join([
    "ENTRY %main (w: bf16[64,256]) -> bf16[8,256] {",
    _instr("w", "parameter", "bf16[64,256]", []),
    _instr("slice-start.1", "slice-start", "bf16[64,256]", ["w"]),
    _instr("slice-done.1", "slice-done", "bf16[32,256]", ["slice-start.1"]),
    _instr("fusion.1", "fusion", "bf16[8,256]", ["slice-done.1"],
           "M/model/layers_0/mlp/gate_proj/dot_general"),
    _instr("fusion.2", "fusion", "bf16[8,64]", ["fusion.1"],
           "M/model/layers_0/self_attn/q_proj/dot_general"),
    _instr("paged_decode_attention.3", "custom-call", "bf16[8,2,128]",
           ["fusion.2"], "M/model/layers_0/self_attn/jit(_decode)/"
           "paged_decode_attention/pallas_call"),
    _instr("fusion.4", "fusion", "bf16[8,64]", ["w"],
           "M/model/layers_1/conv/in_proj/dot_general"),
    _instr("fusion.5", "fusion", "f32[8]", ["fusion.4"],
           "M/model/layers_1/operator_norm/rsqrt"),
    _instr("ragged-dot-gmm.6", "custom-call", "bf16[32,64]", ["fusion.5"],
           "M/model/layers_1/feed_forward/moe.experts/gmm/pallas_call"),
    _instr("fusion.7", "fusion", "f32[8]", ["ragged-dot-gmm.6"],
           "M/model/layers_1/ffn_norm/rsqrt"),
    _instr("fusion.8", "fusion", "bf16[8,256]", ["fusion.7"],
           "M/lm_head/dot_general"),
    _instr("fusion.9", "fusion", "s32[8]", ["fusion.8"],
           "sample/sample_logits_batched/argmax"),
    _instr("fusion.10", "fusion", "bf16[8,64]", ["w"],
           "M/model/embed_tokens/gather"),
    _instr("while.11", "while", "bf16[8,64]", ["fusion.10"], "while"),
    _instr("lone.12", "constant", "s32[]", []),
    "}"])
# the train step: one matmul forward, recomputed and backward, the update
TRAIN = "\n".join([
    "ENTRY %main (w: f32[64,256]) -> f32[64,256] {",
    _instr("w", "parameter", "f32[64,256]", []),
    _instr("fusion.1", "fusion", "bf16[8,256]", ["w"],
           "jvp(L)/model/layers/block/mlp/up_proj/dot_general"),
    _instr("fusion.2", "fusion", "bf16[8,256]", ["w"],
           "transpose(jvp(L))/model/while/body/checkpoint/"
           "rematted_computation/layers/block/mlp/up_proj/dot_general"),
    _instr("flash_bwd_dq.3", "custom-call", "f32[8,64]", ["fusion.2"],
           "transpose(jvp(L))/model/layers/block/self_attn/shard_map/"
           "flash_bwd_dq/pallas_call"),
    _instr("fusion.4", "fusion", "f32[64,256]", ["flash_bwd_dq.3"],
           "optim/mul"),
    "}"])

# (result line in the trace, start ms, duration ms)
DECODE_OPS = [
    ("while.11 = bf16[8,64]{1,0} while(%fusion.10)", 0, 60),     # self: 2
    ("slice-done.1 = bf16[32,256]{1,0:S(1)} slice-done(%slice-start.1)",
     1, 4),
    ("fusion.1 = bf16[8,256]{1,0} fusion(%slice-done.1)", 5, 10),
    ("fusion.2 = bf16[8,64]{1,0} fusion(%fusion.1)", 15, 4),
    ("paged_decode_attention.3 = bf16[8,2,128]{2,1,0} custom-call(...)",
     19, 6),
    ("fusion.4 = bf16[8,64]{1,0} fusion(%w)", 25, 3),
    ("fusion.5 = f32[8]{0} fusion(%fusion.4)", 28, 1),
    ("ragged-dot-gmm.6 = bf16[32,64]{1,0} custom-call(%fusion.5)", 29, 16),
    ("fusion.7 = f32[8]{0} fusion(%ragged-dot-gmm.6)", 45, 1),
    ("fusion.8 = bf16[8,256]{1,0} fusion(%fusion.7)", 46, 5),
    ("fusion.9 = s32[8]{0} fusion(%fusion.8)", 51, 1),
    ("fusion.10 = bf16[8,64]{1,0} fusion(%w)", 52, 2),
    ("lone.12 = s32[] constant()", 54, 2),
    ("stale.13 = bf16[8,64]{1,0} fusion(%w)", 56, 3),
]
EAGER_OPS = [("fusion.1 = u32[2]{0} fusion(%p)", 70, 5)]
TRAIN_OPS = [
    ("fusion.1 = bf16[8,256]{1,0} fusion(%w)", 80, 4),
    ("fusion.2 = bf16[8,256]{1,0} fusion(%w)", 84, 4),
    ("flash_bwd_dq.3 = f32[8,64]{1,0} custom-call(%fusion.2)", 88, 6),
    ("fusion.4 = f32[64,256]{1,0} fusion(%flash_bwd_dq.3)", 94, 6),
]


def _planes():
    ops = DECODE_OPS + EAGER_OPS + TRAIN_OPS
    return {"/device:TPU:0": {
        "XLA Modules": [("jit_ragged_decode_block(1)", 0, 60 * MS),
                        ("jit__threefry_split(2)", 70 * MS, 5 * MS),
                        ("jit_train_step(3)", 80 * MS, 20 * MS)],
        "XLA Ops": [("%" + n, a * MS, d * MS) for n, a, d in ops]}}


@pytest.fixture
def tables():
    from deepspeed_tpu.telemetry import device_scopes as ds

    return {"ragged_decode_block": [ds.scope_table(DECODE)],
            "train_step": [ds.scope_table(TRAIN)]}


@pytest.fixture
def ctx(tables, monkeypatch):
    planes = _planes()
    monkeypatch.setattr(scope_trace, "_tables", lambda: (tables, 0.0, 0))
    monkeypatch.setattr(scope_trace.host_spans, "load", lambda ctx: object())
    monkeypatch.setattr(scope_trace.glob, "glob", lambda pattern: ["."])
    monkeypatch.setattr(TR, "newest_xplane", lambda d: "hand")
    monkeypatch.setattr(TR, "load_xplane", lambda path: planes)
    return {"cell": types.SimpleNamespace(name="cell"),
            "trace": TR.reduce_planes(planes)}


def test_the_join(tables):
    rows = scope_trace.join(_planes(), tables)
    by = {r[0]: r[1:5] for r in rows}
    assert by["slice-done"] == ("mlp", "gate_proj", "-", "user")
    assert by["paged_decode_attention custom-call"] == (
        "attn", "paged_decode_attention", "-", "own")
    assert by["ragged-dot-gmm custom-call"] == ("moe", "moe.experts", "-",
                                                "own")
    assert by["while"] == ("rest", None, "-", "own")
    assert by["flash_bwd_dq custom-call"] == ("attn", "flash_bwd_dq", "bwd",
                                              "own")
    # the layer's norms follow its mixer and its expert layer
    norms = {r[2]: r[1] for r in rows if r[2] and r[2].endswith("_norm")}
    assert norms == {"operator_norm": "conv", "ffn_norm": "moe"}
    # no scope within three hops, and a name the text does not hold
    assert by["lone constant"] == (scope_trace.UNSCOPED, None, "-", "none")
    assert by["stale fusion"] == (scope_trace.UNSCOPED, None, "-", "none")
    # a program the registry does not know is ``rest`` by its name
    eager = [r for r in rows if r[4] == "program"]
    assert [(r[1], r[2]) for r in eager] == [("rest", "_threefry_split")]


def test_families_and_the_log_lines_rest_sum_to_100(ctx, capsys):
    got = {n: m.read(ctx) for n, m in FAMILIES.items()}
    logged = [json.loads(l) for l in capsys.readouterr().out.splitlines()
              if l.startswith('{"device_scopes"')]
    assert len(logged) == 1                 # one line a run, however many
    log = logged[0]["device_scopes"]        # readers asked
    total = log["total_s"]
    assert total == pytest.approx(0.085) == pytest.approx(ctx["trace"].busy_s)
    # attn: q_proj 4 + kernel 6 + flash 6; mlp: slice-done 4 + gate 10 + the
    # train step's two up_proj 8; moe: 16 + its norm 1; conv: 3 + its norm 1;
    # head + sample: 5 + 1; unscoped 2 + 3
    want = {"scope_attn_pct": 16, "scope_mlp_pct": 22, "scope_moe_pct": 17,
            "scope_conv_pct": 4, "scope_head_pct": 6,
            "device_unscoped_pct": 5}
    for name, ms in want.items():
        assert got[name] == pytest.approx(100 * ms / 85), name
    kinds = sum(got[n] for n in want)
    rest = sum(log["by_kind"][k] for k in ("embed", "rest", "zero", "optim"))
    # embed 2, the while's own 2 and the eager program 5, the update 6
    assert rest == pytest.approx(0.002 + 0.002 + 0.005 + 0.006)
    assert kinds + 100 * rest / total == pytest.approx(100.0)
    assert sum(log["by_kind"].values()) == pytest.approx(total)
    assert sum(log["via"].values()) == pytest.approx(total)
    assert sum(log["by_phase"].values()) == pytest.approx(total)
    # the phases are the train step's
    assert got["scope_remat_pct"] == pytest.approx(100 * 4 / 85)
    assert got["scope_bwd_pct"] == pytest.approx(100 * 6 / 85)
    assert got["scope_optim_pct"] == pytest.approx(100 * 6 / 85)
    assert log["unscoped_ops"] == [["stale fusion", pytest.approx(0.003)],
                                   ["lone constant", pytest.approx(0.002)]]
    assert ["mlp/gate_proj", pytest.approx(0.014)] in log["by_kind_sub"]
    assert ["ragged-dot-gmm custom-call", pytest.approx(0.016), [
        ["moe/moe.experts via own", pytest.approx(0.016)]]] in log["by_op"]
    assert ["slice-done", pytest.approx(0.004), [
        ["mlp/gate_proj via user", pytest.approx(0.004)]]] in log["by_op"]
    assert log["via"]["program"] == pytest.approx(0.005)
    assert ["train_step: mlp/up_proj", pytest.approx(0.008)] in \
        log["by_program_sub"]
    assert log["programs"] == {"ragged_decode_block": 1, "train_step": 1}


def test_every_reader_returns_none_with_an_empty_registry(ctx, monkeypatch):
    monkeypatch.setattr(scope_trace, "_tables", lambda: ({}, 0.0, 0))
    assert {m.read(dict(ctx)) for m in FAMILIES.values()} == {None}
    # and where the program has no such module at all
    monkeypatch.setattr(scope_trace, "_tables", lambda: None)
    assert {m.read(dict(ctx)) for m in FAMILIES.values()} == {None}
    # and where the run left no trace of its own
    monkeypatch.setattr(scope_trace.host_spans, "load", lambda ctx: None)
    assert {m.read(dict(ctx)) for m in FAMILIES.values()} == {None}


def test_the_real_registry_is_empty_without_an_engine():
    assert scope_trace._tables()[0] == {}


def _ffn_kinds(config):
    """Which kinds a configuration's feed-forward halves have, from the
    source's own keys: ``moe`` where it has routed experts, ``mlp`` where
    some layer is dense or an expert layer has a shared (dense) expert
    beside it.  Experts in EVERY layer and no shared one: no ``mlp``."""
    count = lambda *keys: max(int(config.get(k) or 0) for k in keys)
    if count("num_experts", "n_routed_experts", "num_local_experts",
             "moe_num_primary_experts") < 2:
        return {"mlp"}
    dense = (count("num_dense_layers", "first_k_dense_replace")
             or len(config.get("mlp_only_layers") or [])
             or count("decoder_sparse_step") > 1
             or count("n_shared_experts", "num_shared_experts"))
    return {"moe", "mlp"} if dense else {"moe"}


def test_which_cells_report_each_scope_reader():
    """Read through ``Cell.metrics``, so a bare entry with a list and a
    suffixed entry of one cell count alike.  Which cells are expected is
    read from the configurations' files (``kind``, ``layer_types``, the
    expert keys), so the next configuration needs no edit here."""
    m = H.manifest()
    by_reader = {}
    want = {name: set() for name in FAMILIES}
    for w in m["workloads"]:
        cell = H.Cell(w["name"])
        for e in cell.metrics("per_layer"):
            reader = H.reader_of(e["name"])
            if reader in FAMILIES:
                assert e["unit"] == "%" and e["source"] == "device_trace"
                by_reader.setdefault(reader, set()).add(w["name"])
        kinds = {"attn", "head"} | _ffn_kinds(cell.config)
        if "conv" in (cell.config.get("layer_types") or []):
            kinds.add("conv")
        if cell.config["kind"] == "train":
            kinds |= {"remat", "bwd", "optim"}
        for kind in kinds:
            want[f"scope_{kind}_pct"].add(w["name"])
        want["device_unscoped_pct"].add(w["name"])
    assert by_reader == want
    # the rule, on the shapes the benchmark has
    assert _ffn_kinds({"num_experts": 64}) == {"moe"}
    assert _ffn_kinds({"num_experts": 128, "decoder_sparse_step": 1,
                       "mlp_only_layers": []}) == {"moe"}
    assert _ffn_kinds({"num_experts": 64, "num_dense_layers": 2}) == \
        {"moe", "mlp"}
    assert _ffn_kinds({"n_routed_experts": 320, "n_shared_experts": 1}) == \
        {"moe", "mlp"}
    assert _ffn_kinds({"intermediate_size": 14336}) == {"mlp"}
    # SmallThinker's key for its routed experts (PR 64's cell; the rule read
    # it as a dense model until PR 68)
    assert _ffn_kinds({"moe_num_primary_experts": 64}) == {"moe"}
