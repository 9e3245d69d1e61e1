"""The trace-to-metrics reduction on a synthetic two-device trace with known
numbers, and on the small trace recorded on the chip."""

from pathlib import Path

import pytest

from bench_matrix.reduce import intervals as iv
from bench_matrix.reduce import xplane

MS = 1_000_000  # ns


def _two_devices():
    """100 ms window. Device 0: fusion 0-40, all-gather 40-50 (exposed),
    flash 50-80, idle 80-90, fusion 90-100. Device 1: fusion 0-100 on the
    ops line, with an all-reduce in flight 20-40 under it (hidden)."""
    t = xplane.Trace()
    t.devices["/device:TPU:0"] = [
        ("fusion.1 fusion f32[8]", 0, 40 * MS),
        ("all-gather-done.2 all-gather-done bf16[8]", 40 * MS, 50 * MS),
        ("attn.3 custom-call bf16[8] tpu_custom_call", 50 * MS, 80 * MS),
        ("fusion.4 fusion f32[8]", 90 * MS, 100 * MS),
    ]
    t.devices["/device:TPU:1"] = [("fusion.1 fusion f32[8]", 0, 100 * MS)]
    t.in_flight["/device:TPU:1"] = [
        ("all-reduce-start.7 all-reduce-start f32[8]", 20 * MS, 40 * MS)]
    t.modules["/device:TPU:0"] = [("jit_step(123)", 0, 100 * MS)]
    t.spans = [("engine.step", 0, 85 * MS), ("submit", 85 * MS, 100 * MS)]
    return t


def test_interval_arithmetic():
    assert iv.union([(5, 7), (0, 2), (1, 3), (7, 7)]) == [(0, 3), (5, 7)]
    assert iv.measure([(0, 2), (1, 3)]) == 3
    assert iv.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert iv.gaps([(2, 3)], 0, 5) == [(0, 2), (3, 5)]


def test_busy_idle_kernel_and_collective_numbers_on_two_devices():
    t = _two_devices()
    b = xplane.busy(t)
    assert b["window_s"] == pytest.approx(0.1)
    assert b["busy_s"]["/device:TPU:0"] == pytest.approx(0.09)
    assert b["idle_share"]["/device:TPU:0"] == pytest.approx(0.1)
    assert b["idle_share"]["/device:TPU:1"] == pytest.approx(0.0)
    k = xplane.time_matching(t, "tpu_custom_call$")
    assert k["/device:TPU:0"] == {"seconds": pytest.approx(0.03), "events": 1}
    assert k["/device:TPU:1"]["events"] == 0
    c = xplane.collectives(t)
    assert c["/device:TPU:0"]["collective_s"] == pytest.approx(0.01)
    assert c["/device:TPU:0"]["exposed_s"] == pytest.approx(0.01)
    assert c["/device:TPU:1"]["collective_s"] == pytest.approx(0.02)
    assert c["/device:TPU:1"]["exposed_s"] == pytest.approx(0.0)


def test_breakdown_names_ops_and_attributes_gaps():
    t = _two_devices()
    ops = dict(xplane.top_ops(t, 10))
    # fusion.1 and fusion.4 give the same shape: one name; mean over devices
    assert ops["fusion fusion f32[8]"] == pytest.approx((0.05 + 0.1) / 2)
    assert ops["attn custom-call bf16[8] tpu_custom_call"] == pytest.approx(0.015)
    assert xplane.module_seconds(t) == [["jit_step", pytest.approx(0.1), 1]]
    # no gap in which NO device ran: device 1 is always busy
    assert xplane.idle_gaps(t) == []
    del t.devices["/device:TPU:1"]
    assert xplane.idle_gaps(t) == [["engine.step", pytest.approx(0.01)]]
    assert xplane.idle_per_span(t, "engine.step") == pytest.approx(0.01)
    assert xplane.idle_per_span(t, "no such span") is None


def test_label_cuts_hlo_text_to_instruction_opcode_and_shape():
    text = ('%attn.27 = (bf16[64,4096,128]{2,1,0:T(8,128)(2,1)}, f32[64,4096,1]{2,1,0}) '
            'custom-call(bf16[64,4096,128]{2,1,0} %bitcast.1), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert xplane.label(text) == "attn.27 custom-call bf16[64,4096,128] tpu_custom_call"
    assert xplane.label("%fusion.8 = f32[2,4096]{1,0:T(2,128)S(1)} fusion(f32[2]{0} %p), "
                        "kind=kLoop") == "fusion.8 fusion f32[2,4096]"
    assert xplane.label("%all-gather-start.3 = (bf16[4,8]{1,0}, bf16[16,8]{1,0}) "
                        "all-gather-start(bf16[4,8]{1,0} %p)").startswith("all-gather-start.3 all-gather-start")
    assert xplane.COLLECTIVE.match(xplane.label("%reduce-scatter.1 = f32[8]{0} reduce-scatter(f32[32]{0} %g)"))
    assert xplane.COLLECTIVE.match(xplane.label(
        "%async-collective-done.9 = bf16[4096,14336]{1,0} fusion(bf16[1024,14336]{1,0} %p), kind=kCustom"))
    assert not xplane.COLLECTIVE.match(xplane.label("%slice-start.2 = bf16[8]{0} async-start(bf16[9]{0} %p)"))
    assert xplane.label("not hlo at all") == "not hlo at all"


def test_readers_on_the_synthetic_trace():
    from bench_matrix import spec
    from bench_matrix.readers import ReadEnv

    env = ReadEnv(cell={}, samples={}, trace=_two_devices(), peaks={}, chips=2,
                  memory_peak_bytes=12_000_000_000, say=lambda s: None)
    read = lambda reader, **args: spec.module("readers", reader).read(args, env)
    assert read("trace_idle") == pytest.approx(5.0)
    assert read("trace_collective", which="total") == pytest.approx(15.0)
    assert read("trace_collective", which="exposed") == pytest.approx(5.0)
    assert read("trace_share", pattern="tpu_custom_call$") == pytest.approx(100 * (0.03 / 0.09) / 2)
    assert read("trace_share", pattern="no_such_kernel") is None
    assert read("span_host", span="engine.step") == pytest.approx(0.0)  # device 1 never idle
    assert read("memory_peak") == pytest.approx(12.0)
    env.trace = None
    assert read("trace_idle") is None and read("span_host", span="engine.step") is None


FIXTURE = Path(xplane.__file__).resolve().parents[1] / "fixtures" / "v5e_small.xplane.pb"


def test_reduction_on_the_trace_recorded_on_the_chip():
    """`fixtures/v5e_small.xplane.pb`: recorded on one TPU v5 lite by
    `bench_matrix/fixtures/record.py`; the numbers below were read from it by
    hand (`xplane.describe`) when it was recorded."""
    import json

    want = json.loads((FIXTURE.parent / "v5e_small.json").read_text())
    t = xplane.load(str(FIXTURE))
    assert sorted(t.devices) == want["devices"]
    b = xplane.busy(t)
    assert b["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    dev = want["devices"][0]
    assert b["busy_s"][dev] == pytest.approx(want["busy_s"], rel=1e-9)
    k = xplane.time_matching(t, want["kernel_pattern"])[dev]
    assert k["events"] == want["kernel_events"]
    assert k["seconds"] == pytest.approx(want["kernel_s"], rel=1e-9)
    assert {s[0] for s in t.spans} == set(want["spans"])
    assert xplane.top_ops(t, 3)[0][0] == want["top_op"]
    assert xplane.idle_per_span(t, "step dispatch") == pytest.approx(
        want["idle_per_dispatch_s"], rel=1e-9)
    assert [m[0] for m in xplane.module_seconds(t)] == ["jit_work"]
