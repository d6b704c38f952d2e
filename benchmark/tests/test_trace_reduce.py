"""The trace reduction on a hand-built trace, and its loader on a
hand-encoded ``xplane.pb``."""
import pytest

from benchmark import trace_reduce as T

MS = 1e6


def planes():
    # chip 0: program a (0-10 ms) holds a while (0-10) around a fusion
    # (1-4) and an all-gather (4-6); idle 10-15; program b (15-25) runs a
    # fusion (15-19), idles 19-21, then waits in an all-reduce-done (21-25)
    chip0 = {"XLA Modules": [("jit_a(1)", 0, 10 * MS),
                             ("jit_b(2)", 15 * MS, 10 * MS)],
             "XLA Ops": [("while", 0, 10 * MS), ("fusion.1", 1 * MS, 3 * MS),
                         ("all-gather.2", 4 * MS, 2 * MS),
                         ("fusion.3", 15 * MS, 4 * MS),
                         ("all-reduce-done.1", 21 * MS, 4 * MS)]}
    # chip 1: busy 0-25 ms in one program, no collective
    chip1 = {"XLA Modules": [("jit_a(1)", 0, 25 * MS)],
             "XLA Ops": [("fusion.1", 0, 25 * MS)]}
    return {"/device:TPU:0": chip0, "/device:TPU:1": chip1}


def test_busy_union_idle_and_programs():
    r = T.reduce_planes(planes())
    assert r.chips == 2
    assert r.window_s == pytest.approx(0.025)
    # chip 0 busy 10 + 4 + 4 = 18 ms, chip 1 25 ms: mean 21.5
    assert r.busy_s == pytest.approx(0.0215)
    assert r.idle_share == pytest.approx(1 - 21.5 / 25)
    assert r.program_s["jit_a"] == pytest.approx((0.010 + 0.025) / 2)
    assert r.program_s["jit_b"] == pytest.approx(0.010 / 2)
    assert r.program_runs == {"jit_a": 1.0, "jit_b": 0.5}


def test_self_time_and_collective_exposure():
    r = T.reduce_planes(planes())
    # the while's self time is what its body does not cover: 10 - 3 - 2
    assert T.short_name("%fusion.5 = bf16[8]{0} fusion(bf16[8,4096]{1,0} "
                        "%all-gather-done.3), kind=kLoop") == \
        "fusion bf16[8,4096]"         # an operand does not name the op
    assert r.op_self_s["while"] == pytest.approx(0.005 / 2)
    # exposed: all-gather 2 ms + all-reduce-done 4 ms on chip 0, mean of 2
    assert r.collective_exposed_s == pytest.approx(0.006 / 2)


def test_gaps_are_charged_to_what_runs_next():
    b = T.reduce_planes(planes()).breakdown()
    gaps = dict(map(tuple, b["idle_gaps"]))
    assert gaps["unattributed, before jit_b"] == pytest.approx(0.005 / 2)
    assert gaps["unattributed, before the rest of jit_b"] == \
        pytest.approx(0.002 / 2)
    assert b["device_ops"][0][0] == "fusion"   # fusion.1 + fusion.3, numbers dropped
    assert len(b["device_ops"]) <= 10


def test_no_device_plane_is_an_error_not_an_empty_answer():
    with pytest.raises(T.TraceError):
        T.reduce_planes({})
    with pytest.raises(T.TraceError):
        T.reduce_planes({"/device:TPU:0": {"Steps": []}})
    with pytest.raises(T.TraceError):
        T.newest_xplane("/nonexistent")


# -- a hand-encoded xplane.pb: XSpace{planes{name, lines{name, timestamp_ns,
# events{metadata_id, offset_ps, duration_ps}}, event_metadata{id -> name}}}

def _varint(n):
    out = b""
    while True:
        b7, n = n & 0x7F, n >> 7
        out += bytes([b7 | (0x80 if n else 0)])
        if not n:
            return out


def _field(num, wire, payload):
    key = _varint(num << 3 | wire)
    return key + (_varint(len(payload)) + payload if wire == 2
                  else _varint(payload))


def _xspace(plane_name, line_name, events):
    meta, evs = b"", b""
    for i, (name, offset_ps, dur_ps) in enumerate(events, start=1):
        md = _field(1, 0, i) + _field(2, 2, name.encode())
        meta += _field(4, 2, _field(1, 0, i) + _field(2, 2, md))
        evs += _field(4, 2, _field(1, 0, i) + _field(2, 0, offset_ps)
                      + _field(3, 0, dur_ps))
    line = _field(1, 0, 1) + _field(2, 2, line_name.encode()) + \
        _field(3, 0, 1000) + evs
    plane = _field(1, 0, 1) + _field(2, 2, plane_name.encode()) + \
        _field(3, 2, line) + meta
    return _field(1, 2, plane)


def test_loader_reads_a_hand_encoded_xplane(tmp_path):
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace(
        "/device:TPU:0", "XLA Ops",
        [("fusion.1", 0, 2_000_000_000), ("all-gather.1", 3_000_000_000,
                                          1_000_000_000)]))
    got = T.load_xplane(T.newest_xplane(str(tmp_path)))
    evs = got["/device:TPU:0"]["XLA Ops"]
    assert [e[0] for e in evs] == ["fusion.1", "all-gather.1"]
    assert evs[0][2] == pytest.approx(2e6)          # 2 ms in ns
    assert evs[1][1] - evs[0][1] == pytest.approx(3e6)
    r = T.reduce_dir(str(tmp_path))
    assert r.busy_s == pytest.approx(0.003)
    assert r.window_s == pytest.approx(0.004)
    assert r.collective_exposed_s == pytest.approx(0.001)
