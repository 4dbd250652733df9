"""Device time by scope: ``perfbench/scopes.py`` and the four readers built on
it, on hand-made events with hand-worked answers and on a pair recorded on
the chip (PR 25): two train steps of GPT-2 large, and the scope table the
program gave for the same executable."""
import gzip
import importlib.util
import json
import os
import shutil

import pytest

import _paths
import scopes
import xplane

from apex_tpu.monitor.trace import split_scope

MS = 1e6  # ns


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(_paths.PERFBENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("text,want", [
    ("%copy.49 = s32[2]{0:T(128)} copy(s32[2]{0} %p)", ("copy.49", "copy")),
    ("%flash_fwd.13 = (bf16[320,1024,64]{2,1,0:T(8,128)(2,1)}, f32[320,1024,1]{2,1,0:T(8,128)}) "
     "custom-call(s32[3]{0} %x)", ("flash_fwd.13", "custom-call")),
    ("%slice-done.11 = bf16[4,20]{1,0:T(8,128)(2,1)S(1)} async-done(((bf16[16,20]{1,0:T(8,128)(2,1)}), "
     "bf16[4,20]{1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) %slice-start.11)", ("slice-done.11", "async-done")),
    ("%while.4 = (s32[]{:T(128)}, bf16[16,1024]{1,0:T(8,128)(2,1)S(1)}) while(%t)", ("while.4", "while")),
    ("jit_train_step(2785582291449536894)", ("", "")),
])
def test_parse_event(text, want):
    assert scopes.parse_event(text) == want


def _rec(opcode, op_name="", moves=False, operands=(), container=False):
    return {"op_name": op_name, "opcode": opcode, "moves_only": moves,
            "container": container, "operands": list(operands)}


FWD = "jit(train_step)/jvp()/while/body/closed_call/layer/"
TABLE = {
    "while.1": _rec("while", "jit(train_step)/jvp()/while", container=True),
    "fusion.2": _rec("fusion", FWD + "attn/qkv/dot_general"),
    "copy.3": _rec("copy", "", moves=True, operands=["fusion.2"]),           # lent by its operand
    "copy.4": _rec("copy", "", moves=True, operands=["param.9"]),            # lent by its user
    "flash_fwd.5": _rec("custom-call", FWD + "attn/core/flash_fwd/pallas_call", operands=["copy.4"]),
    "copy.6": _rec("copy", "", moves=True, operands=["param.9"]),            # nobody to borrow from
    "param.9": _rec("parameter", "params['layers']['qkv_kernel']"),
    "dus.7": _rec("dynamic-update-slice", "jit(train_step)/transpose(jvp())/while/body/dynamic_update_slice",
                  moves=True),
    "adam.8": _rec("custom-call", "jit(train_step)/opt/adam_tail/pallas_call"),
}


def _events():
    # one execution of 20 ms: a while of 10 ms nesting three operations that
    # take 8 of them, then four operations outside it
    return [("%while.1 = (s32[]{:T(128)}) while(%t)", 0, 10 * MS),
            ("%fusion.2 = bf16[8]{0} fusion(%a)", 1 * MS, 3 * MS),
            ("%copy.3 = bf16[8]{0} copy(%fusion.2)", 4 * MS, 1 * MS),
            ("%flash_fwd.5 = (bf16[8]{0:T(8,128)(2,1)}) custom-call(%copy.4)", 5 * MS, 4 * MS),
            ("%copy.4 = bf16[8]{0} copy(%param.9)", 10 * MS, 2 * MS),
            ("%copy.6 = bf16[8]{0} copy(%param.9)", 12 * MS, 1 * MS),
            ("%dus.7 = bf16[8]{0} dynamic-update-slice(%a, %b)", 13 * MS, 3 * MS),
            ("%adam.8 = (f32[8]{0}) custom-call(%g)", 16 * MS, 4 * MS)]


def _trace(events, n_runs=1):
    ops, modules = [], []
    for r in range(n_runs):
        off = r * 20 * MS
        ops += [(t, s + off, d) for t, s, d in events]
        modules.append(("jit_train_step(7)", off, 20 * MS))
    return xplane.Trace({"/device:TPU:0": xplane.DeviceTrace(ops, modules)}, [])


def test_by_scope_takes_self_time_and_lends_scopes_one_hop():
    got = scopes.by_scope(_trace(_events(), n_runs=2), "jit_train_step", (TABLE, split_scope))
    rows = {k: round(v * 1e3, 6) for k, v in got["rows"].items()}      # ms a step
    assert rows == {
        ("fwd", "(none)", False): 2.0,                  # the while's own time: 10 - 3 - 1 - 4
        ("fwd", "layer/attn/qkv", False): 3.0,
        ("fwd", "layer/attn/qkv", True): 1.0,           # copy.3, its operand's scope
        ("fwd", "layer/attn/core/flash_fwd", False): 4.0,
        ("fwd", "layer/attn/core/flash_fwd", True): 2.0,    # copy.4, its user's scope
        ("(none)", "(none)", True): 1.0,                # copy.6
        ("bwd", "scan_carry", True): 3.0,
        ("opt", "opt/adam_tail", False): 4.0,
    }
    assert got["step_s"] == pytest.approx(0.020)        # self times add up to the busy time
    assert got["own_s"] == pytest.approx(0.014)         # borrowed scopes do not count as own
    assert got["matched"] == 1.0 and got["missed"] == []
    assert scopes.share(got, lambda k: k[2]) == pytest.approx(35.0)
    assert scopes.table_by_scope(got, top=2) == [
        ["fwd", "layer/attn/core/flash_fwd", pytest.approx(0.006), pytest.approx(0.002)],
        ["fwd", "layer/attn/qkv", pytest.approx(0.004), pytest.approx(0.001)]]


def test_a_renamed_instruction_fails_the_identity_check(capsys):
    # the table is of another executable: the kernel has another name, and a
    # fusion's name now belongs to a copy
    events = [(t.replace("%flash_fwd.5", "%flash_fwd.6"), s, d) for t, s, d in _events()]
    got = scopes.by_scope(_trace(events), "jit_train_step", (TABLE, split_scope))
    assert got["matched"] == pytest.approx(0.8)
    assert got["missed"] == [["flash_fwd.6", pytest.approx(0.004)]]
    table = dict(TABLE, **{"fusion.2": _rec("copy", FWD + "attn/qkv/transpose")})
    got = scopes.by_scope(_trace(_events()), "jit_train_step", (table, split_scope))
    assert got["missed"] == [["fusion.2", pytest.approx(0.003)]]
    # and every reader then returns nothing, with one line that says why
    facts = {"kind": "train", "rows": 16, "seq": 1024, "model": {"name": "toy"},
             "_scope_tables": {"jit_train_step": (table, split_scope)}}
    for name in ("train_scope_coverage_pct", "train_recompute_pct", "train_layout_pct"):
        assert reader(name).read(facts, _trace(_events())) is None
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(lines) == 1 and lines[0]["missed"][0][0] == "fusion.2"


def test_readers_return_nothing_where_the_program_has_no_registry():
    facts = {"kind": "train", "rows": 16, "seq": 1024, "model": {"name": "toy"},
             "_scope_tables": {"jit_train_step": None}}
    for name in ("train_scope_coverage_pct", "train_recompute_pct", "train_layout_pct"):
        assert reader(name).read(facts, _trace(_events())) is None
        assert reader(name).read(dict(facts), None) is None


def test_the_three_scope_readers_on_hand_made_events(capsys):
    facts = {"kind": "train", "rows": 16, "seq": 1024, "model": {"name": "toy"},
             "_scope_tables": {"jit_train_step": (TABLE, split_scope)}}
    trace = _trace(_events())
    assert reader("train_scope_coverage_pct").read(facts, trace) == pytest.approx(70.0)
    assert reader("train_recompute_pct").read(facts, trace) == pytest.approx(0.0)
    assert reader("train_layout_pct").read(facts, trace) == pytest.approx(35.0)
    line, = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert line["info"] == "toy" and line["by_scope"][0][:2] == ["fwd", "layer/attn/core/flash_fwd"]
    assert line["moves_only"] == {"attn/*.fwd": pytest.approx(0.003), "scan_carry": pytest.approx(0.003),
                                  "(none)": pytest.approx(0.001)}
    assert sum(line["by_phase"].values()) == pytest.approx(line["step_busy_s"])


def test_collective_exposed_pct_counts_a_done_that_waits(capsys):
    # two steps of 100 ms on two devices. The all-reduce is started behind the
    # backward's last fusion and its -done waits 6 ms for the links on one
    # device and 2 ms on the other; the -start itself takes 0.1 ms
    def device(wait_ms):
        ops, modules = [], []
        for r in range(2):
            off = r * 100 * MS
            modules.append(("jit_train_step(7)", off, 100 * MS))
            ops += [("%fusion.1 = bf16[8]{0} fusion(%a)", off, 80 * MS),
                    ("%all-reduce-start.2 = (bf16[8]{0}, bf16[8]{0}) all-reduce-start(%g)", off + 80 * MS, 0.1 * MS),
                    ("%fusion.3 = bf16[8]{0} fusion(%b)", off + 80.1 * MS, 10 * MS),
                    ("%all-reduce-done.2 = bf16[8]{0} all-reduce-done(%all-reduce-start.2)",
                     off + 90.1 * MS, wait_ms * MS),
                    ("%adam.4 = (f32[8]{0}) custom-call(%g)", off + 90.1 * MS + wait_ms * MS, 3 * MS)]
        return xplane.DeviceTrace(ops, modules)

    trace = xplane.Trace({"/device:TPU:0": device(2.0), "/device:TPU:1": device(6.0)}, [])
    facts = {"kind": "train", "rows": 64, "seq": 1024, "model": {"name": "toy"},
             "_scope_tables": {"jit_train_step": None}}
    got = reader("collective_exposed_pct").read(facts, trace)
    assert got == pytest.approx(100.0 * (6.0 + 0.1) * 2 / 200.0)       # the worse device
    line, = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert line["collectives"][0][0] == "all-reduce-done.2"
    assert line["collectives"][0][1] == pytest.approx(0.006)
    # one device and no collective: nothing to report
    one = xplane.Trace({"/device:TPU:0": xplane.DeviceTrace(
        [("%fusion.1 = bf16[8]{0} fusion(%a)", 0, 80 * MS)], [("jit_train_step(7)", 0, 100 * MS)])}, [])
    assert reader("collective_exposed_pct").read(facts, one) is None


# -- the recorded pair ---------------------------------------------------------------

def _recorded(tmp_path_factory, name, rows, chips):
    path = tmp_path_factory.mktemp("xplane") / (name + ".xplane.pb")
    with gzip.open(os.path.join(_paths.DATA, name + ".xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(_paths.DATA, name + ".scopes.json.gz"), "rt") as f:
        table = json.load(f)
    with open(os.path.join(_paths.PERFBENCH, "configs", "gpt2-large.json")) as f:
        model = json.load(f)
    facts = {"kind": "train", "model": model, "rows": rows, "seq": 1024, "chips": chips,
             "_scope_tables": {"jit_train_step": (table, split_scope)}}
    return facts, xplane.load(str(path))


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two steps of ``gpt2-large.train-b16`` (one chip)."""
    return _recorded(tmp_path_factory, "train_scoped", 16, 1)


@pytest.fixture(scope="module")
def recorded_dp4(tmp_path_factory):
    """One step of ``gpt2-large.train-dp4`` on each of four chips."""
    return _recorded(tmp_path_factory, "train_dp4_scoped", 64, 4)


def test_recorded_pair_passes_the_identity_check(recorded):
    facts, trace = recorded
    got = scopes.train_step_scopes(facts, trace)
    assert got["matched"] >= 0.99
    dev, = trace.devices.values()
    assert len(xplane.whole_runs(xplane.module_runs(dev, "jit_train_step"))) == 2
    # phases add up to the step's busy time
    busy = xplane.busy_by_device(trace)["/device:TPU:0"] / 2e9
    assert got["step_s"] == pytest.approx(busy, rel=1e-3)


@pytest.mark.parametrize("name,lo,hi", [
    ("train_scope_coverage_pct", 95.0, 100.0),
    ("train_recompute_pct", 17.0, 21.0),
    ("train_layout_pct", 18.0, 23.0),
])
def test_scope_readers_on_the_recorded_pair(recorded, name, lo, hi, capsys):
    facts, trace = recorded
    assert lo < reader(name).read(facts, trace) < hi
    if name == "train_layout_pct":
        line, = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
        moved = line["moves_only"]
        assert {"scan_carry", "(none)", "rest"} <= set(moved)
        assert any(k.startswith("attn/*.") for k in moved)
        assert sum(moved.values()) == pytest.approx(
            line["step_busy_s"] * reader(name).read(facts, trace) / 100.0, rel=1e-6)


def test_collective_reader_finds_none_on_one_chip(recorded):
    facts, trace = recorded
    assert reader("collective_exposed_pct").read(facts, trace) is None


def test_recorded_dp4_pair_names_the_gradient_all_reduce(recorded_dp4, capsys):
    facts, trace = recorded_dp4
    assert len(trace.devices) == 4
    got = scopes.train_step_scopes(facts, trace)
    assert got["matched"] >= 0.99                   # one table serves the four devices
    assert 95.0 < reader("train_scope_coverage_pct").read(facts, trace) < 100.0
    exposed = reader("collective_exposed_pct").read(facts, trace)
    assert 1.5 < exposed < 3.0                      # 2.2 on the chip (PERF.md, PR 25)
    line, = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    # the per-layer gradients, all-reduced inside the backward scan's body
    name, seconds, phase, scope = line["collectives"][0]
    assert name.startswith("all-reduce") and phase == "bwd" and scope.startswith("layer/")
    assert 0.020 < seconds < 0.028
    assert {c[3] for c in line["collectives"]} >= {"lm_head_loss", "embed"}
