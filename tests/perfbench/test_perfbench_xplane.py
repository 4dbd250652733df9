"""The trace reduction: on hand-made events with hand-worked answers, and on
a small trace recorded on the chip (two GPT-2 large train steps, PR 24)."""
import gzip
import os
import shutil

import pytest

import _paths
import xplane

MS = 1e6  # ns


def test_union_and_busy_time_do_not_count_nested_time_twice():
    # a while op spans 0..10 ms and nests two ops; a lone op follows a gap
    events = [("%while.1 = () while()", 0, 10 * MS),
              ("%fusion.2 = f32[] fusion()", 1 * MS, 3 * MS),
              ("%flash_fwd.3 = () custom-call()", 5 * MS, 4 * MS),
              ("%copy.4 = f32[] copy()", 12 * MS, 2 * MS)]
    busy = xplane.union(xplane.as_intervals(events))
    assert busy == [(0, 10 * MS), (12 * MS, 14 * MS)]
    assert xplane.total(busy) == 12 * MS          # not 19 ms, the sum of durations


def test_self_time_by_name_charges_a_container_only_its_own_time():
    events = [("%while.1 = () while()", 0, 10 * MS),
              ("%fusion.2 = f32[] fusion()", 1 * MS, 3 * MS),
              ("%fusion.7 = f32[] fusion()", 4 * MS, 1 * MS),
              ("%flash_fwd.3 = () custom-call()", 5 * MS, 4 * MS)]
    by_name = xplane.time_by_name(events)
    assert by_name["fusion"] == (4 * MS, 2)
    assert by_name["flash_fwd"] == (4 * MS, 1)
    assert by_name["while"] == (2 * MS, 1)        # 10 - 3 - 1 - 4


@pytest.mark.parametrize("raw,name", [
    ("%flash_fwd.13 = (bf16[320,1024,64]{2,1,0}) custom-call(s32[3]{0} %x)", "flash_fwd"),
    ("%bitcast_dynamic-update-slice_fusion.11 = bf16[36] fusion()", "bitcast_dynamic-update-slice_fusion"),
    ("jit_decode(6590898799803967883)", "jit_decode"),
    ("jit_train_step(2785582291449536894)", "jit_train_step"),
    ("%copy-done.38 = f32[] copy-done()", "copy-done"),
    ("hb.engine_step", "hb.engine_step"),
])
def test_op_name(raw, name):
    assert xplane.op_name(raw) == name


def _trace():
    dev = xplane.DeviceTrace(
        ops=[("%a.1 = f32[] fusion()", 10 * MS, 10 * MS),       # 10..20
             ("%b.2 = f32[] fusion()", 30 * MS, 10 * MS),       # 30..40
             ("%c.3 = f32[] fusion()", 45 * MS, 5 * MS)],       # 45..50
        modules=[("jit_decode(1)", 10 * MS, 10 * MS), ("jit_decode(1)", 30 * MS, 20 * MS)])
    host = [("hb.engine_step", 0, 28 * MS), ("decode", 5 * MS, 20 * MS),
            ("hb.submit", 41 * MS, 3.5 * MS), ("hb.engine_step", 28 * MS, 30 * MS)]
    return xplane.Trace({"/device:TPU:0": dev}, host)


def test_idle_share_and_gap_attribution():
    tr = _trace()
    assert xplane.window_of(tr) == (10 * MS, 50 * MS)
    s = xplane.summary(tr)
    assert s["window_s"] == pytest.approx(0.040)
    assert s["busy_s"] == pytest.approx(0.025)
    assert s["idle_share_max"] == pytest.approx(15 / 40)
    gaps = xplane.gaps_by_host_span(tr, "/device:TPU:0")
    # 20..30 ms: its middle (25 ms) lies in "decode" (20 ms long) and in the
    # first hb.engine_step (28 ms long): the narrowest names it.
    # 40..45 ms: its middle (42.5 ms) lies in hb.submit.
    assert gaps == {"decode": 10 * MS, "hb.submit": 5 * MS}
    assert s["breakdown"]["idle_gaps"][0] == ["decode", pytest.approx(0.010)]
    assert s["breakdown"]["device_ops"][0][0] in ("a", "b")


def test_events_within_and_module_runs():
    tr = _trace()
    dev = tr.devices["/device:TPU:0"]
    runs = xplane.module_runs(dev, "jit_decode")
    assert [d for _, _, d in runs] == [10 * MS, 20 * MS]
    inside = xplane.events_within(dev.ops, [(30 * MS, 50 * MS)])
    assert [xplane.op_name(n) for n, _, _ in inside] == ["b", "c"]


def test_whole_runs_drops_the_execution_the_profiler_cut():
    runs = [("jit_train_step(1)", 0, 400 * MS), ("jit_train_step(1)", 400 * MS, 1169 * MS),
            ("jit_train_step(1)", 1569 * MS, 1170 * MS), ("jit_train_step(1)", 2739 * MS, 1168 * MS),
            ("jit_train_step(1)", 3907 * MS, 600 * MS)]
    assert [d for _, _, d in xplane.whole_runs(runs)] == [1169 * MS, 1170 * MS, 1168 * MS]
    assert xplane.whole_runs([]) == []


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "train_2steps.xplane.pb"
    with gzip.open(os.path.join(_paths.DATA, "train_2steps.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return xplane.load(str(path))


def test_recorded_trace_structure(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    dev = recorded.devices["/device:TPU:0"]
    runs = xplane.module_runs(dev, "jit_train_step")
    assert len(runs) == 2
    assert all(1.16e9 < d < 1.18e9 for _, _, d in runs)      # 1.169 s a step
    assert {n for n, _, _ in recorded.host_spans} == {"hb.batch", "hb.dispatch", "hb.loss_read"}


def test_recorded_trace_kernels_and_idle(recorded):
    dev = recorded.devices["/device:TPU:0"]
    by_name = xplane.time_by_name(dev.ops)
    # 36 layers x 2 steps; the forward runs twice a layer under remat
    assert by_name["flash_fwd"][1] == 144
    assert by_name["flash_bwd_dq"][1] == by_name["flash_bwd_dkv"][1] == 72
    assert by_name["lm_head_fwd"][1] == 2
    # self times add up to the busy time: nothing is counted twice
    busy = xplane.busy_by_device(recorded)["/device:TPU:0"]
    assert sum(ns for ns, _ in by_name.values()) == pytest.approx(busy, rel=1e-3)
    s = xplane.summary(recorded)
    assert s["window_s"] == pytest.approx(2.3389, abs=1e-3)
    assert s["idle_share_max"] < 1e-4       # two steps back to back, dispatched ahead
    assert s["breakdown"]["device_ops"][0][0] == "flash_fwd"
    assert len(s["breakdown"]["device_ops"]) == 10


def test_train_kernel_rooflines_on_the_recorded_trace(recorded):
    import importlib.util
    import json

    import peaks

    with open(os.path.join(_paths.PERFBENCH, "configs", "gpt2-large.json")) as f:
        model = json.load(f)
    facts = {"kind": "train", "model": model, "rows": 16, "seq": 1024, "chips": 1,
             "peaks": peaks.peaks_for("TPU v5 lite")}

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            "m_" + name, os.path.join(_paths.PERFBENCH, "metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    flash = reader("flash_attn_roofline")(facts, recorded)
    head = reader("lm_head_loss_roofline")(facts, recorded)
    # 5.41e12 operations / 197e12 = 27.5 ms against 321.7 ms of kernel time a step
    assert flash == pytest.approx(8.54, abs=0.05)
    # 6.32e12 / 197e12 = 32.1 ms against 58.1 ms
    assert head == pytest.approx(55.2, abs=0.3)
    assert reader("flash_attn_roofline")(facts, None) is None
    assert reader("flash_attn_roofline")(dict(facts, peaks=None), recorded) is None
