"""``adam_step_roofline`` (``perfbench/metrics``): the optimizer's pass over
the parameters a chip holds as a share of what the memory allows, on
hand-made events for both families of configuration."""
import gzip
import json
import os
import shutil

import pytest

import _paths
import counts
import counts_hybrid
import peaks
import run as runner
import xplane

from apex_tpu.monitor.trace import split_scope

MS = 1e6  # ns
OPT = "jit(train_step)/opt/shard_map/"
BWD = "jit(train_step)/transpose(jvp())/while/body/layer/"
V5E = peaks.peaks_for("TPU v5 lite")
read = runner._module(os.path.join(_paths.PERFBENCH, "metrics", "adam_step_roofline.py"),
                      "metric_adam_step_roofline").read


def _rec(opcode, op_name, moves_only=False):
    return {"op_name": op_name, "opcode": opcode, "moves_only": moves_only, "container": False,
            "operands": []}


def _config(name):
    with open(os.path.join(_paths.PERFBENCH, "configs", name + ".json")) as f:
        return json.load(f)


# one step of 100 ms: under ``opt`` a reshape of 10 ms, XLA's fusion of 6 ms
# and the kernel below the scope, 24 ms: 40 ms; the backward's 60 ms is not
# the optimizer's
TABLE = {
    "fusion.1": _rec("fusion", BWD + "mlp/fc/dot_general"),
    "reshape.2": _rec("reshape", OPT + "reshape", moves_only=True),
    "fusion.3": _rec("fusion", OPT + "mul"),
    "adam_tail.4": _rec("custom-call", OPT + "adam_tail/pallas_call"),
}
OPS = [("%fusion.1 = bf16[8]{0} fusion(%a)", 0, 60 * MS),
       ("%reshape.2 = f32[8]{0} reshape(%a)", 60 * MS, 10 * MS),
       ("%fusion.3 = f32[8]{0} fusion(%a)", 70 * MS, 6 * MS),
       ("%adam_tail.4 = (f32[8]{0:T(8,128)}) custom-call(%a)", 76 * MS, 24 * MS)]


def _facts(config, rows, seq, **kw):
    facts = {"kind": "train", "rows": rows, "seq": seq, "chips": 1, "model": _config(config),
             "peaks": V5E, "_scope_tables": {"jit_train_step": (TABLE, split_scope)}}
    return dict(facts, **kw)


def _trace():
    return xplane.Trace({"/device:TPU:0": xplane.DeviceTrace(
        OPS, [("jit_train_step(7)", 0, 100 * MS)])}, [])


@pytest.mark.parametrize("config,rows,seq,n_params", [
    ("gpt2-large", 16, 1024, counts.n_params),
    ("gpt2-medium", 16, 1024, counts.n_params),
    ("olmo-hybrid-7b", 2, 8192, counts_hybrid.n_params),
])
def test_the_share_of_the_roofline_on_hand_made_events(config, rows, seq, n_params):
    """22 bytes a parameter at the chip's 819 GB/s over the 40 ms under
    ``opt`` and ``opt/adam_tail``; the parameters by the configuration's
    family."""
    n = n_params(_config(config))
    assert read(_facts(config, rows, seq), _trace()) == pytest.approx(
        100 * n * 22 / 819e9 / 0.040)


def test_the_counts_are_the_cells():
    assert counts.n_params(_config("gpt2-large")) == 774_030_080
    assert counts.n_params(_config("gpt2-medium")) == 354_823_168
    assert counts_hybrid.n_params(_config("olmo-hybrid-7b")) == 928_862_196


@pytest.mark.parametrize("why,kw,traced", [
    ("no peaks: no chip", {"peaks": None}, True),
    ("no scope table: a program from before the registry",
     {"_scope_tables": {"jit_train_step": None}}, True),
    ("no trace", {}, False),
])
def test_nothing_to_read_reads_nothing(why, kw, traced):
    assert read(_facts("gpt2-large", 16, 1024, **kw), _trace() if traced else None) is None


def test_a_step_with_nothing_under_the_scope_reads_nothing():
    table = {k: v for k, v in TABLE.items() if k == "fusion.1"}
    trace = xplane.Trace({"/device:TPU:0": xplane.DeviceTrace(
        OPS[:1], [("jit_train_step(7)", 0, 100 * MS)])}, [])
    facts = _facts("gpt2-large", 16, 1024, _scope_tables={"jit_train_step": (table, split_scope)})
    assert read(facts, trace) is None


@pytest.mark.parametrize("name,config,rows,seq,percent", [
    ("train_scoped", "gpt2-large", 16, 1024, 19.38),
    ("hybrid_scoped", "olmo-hybrid-7b", 2, 8192, 17.31),
])
def test_the_steps_recorded_with_the_flat_kernel_read_a_fifth(tmp_path, name, config, rows, seq,
                                                               percent):
    """The recorded steps are PR 25's and PR 28's: FusedAdam's flat kernel
    with its reshapes round it, at 107 and 144 ms a step under ``opt``."""
    path = tmp_path / (name + ".xplane.pb")
    with gzip.open(os.path.join(_paths.DATA, name + ".xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(_paths.DATA, name + ".scopes.json.gz"), "rt") as f:
        table = json.load(f)
    facts = _facts(config, rows, seq, _scope_tables={"jit_train_step": (table, split_scope)})
    assert read(facts, xplane.load(str(path))) == pytest.approx(percent, abs=0.01)
