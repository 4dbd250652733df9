"""``delta_rule_kernel_share_pct`` (``perfbench/metrics``): the share of the
device time under ``layer/linattn/core`` that the delta rule's own kernels
take, on hand-made events, on the step recorded before the kernels were
written, and on a step of another family."""
import gzip
import json
import os
import shutil

import pytest

import _paths
import run as runner
import xplane

from apex_tpu.monitor.trace import split_scope

MS = 1e6  # ns
FWD = "jit(train_step)/jvp()/while/body/layer/"
BWD = "jit(train_step)/transpose(jvp())/while/body/layer/checkpoint/"
read = runner._module(os.path.join(_paths.PERFBENCH, "metrics", "delta_rule_kernel_share_pct.py"),
                      "metric_delta_rule_kernel_share_pct").read


def _rec(opcode, op_name, moves_only=False):
    return {"op_name": op_name, "opcode": opcode, "moves_only": moves_only, "container": False,
            "operands": []}


def _config(name):
    with open(os.path.join(_paths.PERFBENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _recorded(tmp_path, name, model, rows, seq):
    path = tmp_path / (name + ".xplane.pb")
    with gzip.open(os.path.join(_paths.DATA, name + ".xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(_paths.DATA, name + ".scopes.json.gz"), "rt") as f:
        table = json.load(f)
    facts = {"kind": "train", "model": _config(model), "rows": rows, "seq": seq, "chips": 1,
             "_scope_tables": {"jit_train_step": (table, split_scope)}}
    return facts, xplane.load(str(path))


def test_share_of_the_core_in_its_kernels_on_hand_made_events():
    """One step of 100 ms. Under the core: the forward kernel 12 ms and 12 ms
    replayed, the backward kernel 20 ms, and round them a transpose of 4 ms
    and an ``einsum`` of 2 ms (a scope below the core, and no kernel): 44 of
    50 ms are the kernels'. The projection before and the FFN after are not
    the core's."""
    table = {
        "fusion.1": _rec("fusion", FWD + "linattn/proj/dot_general"),
        "copy.2": _rec("copy", FWD + "linattn/core/transpose", moves_only=True),
        "delta_rule_fwd.3": _rec("custom-call", FWD + "linattn/core/delta_rule_fwd/pallas_call"),
        "delta_rule_fwd.4": _rec("custom-call", BWD + "rematted_computation/linattn/core/"
                                                      "delta_rule_fwd/pallas_call"),
        "delta_rule_bwd.5": _rec("custom-call", BWD + "linattn/core/delta_rule_bwd/pallas_call"),
        "fusion.6": _rec("fusion", BWD + "linattn/core/bhck,bhkv->bhcv/dot_general"),
        "fusion.7": _rec("fusion", FWD + "mlp/gate_up/dot_general"),
    }
    ops = [("%fusion.1 = bf16[8]{0} fusion(%a)", 0, 10 * MS),
           ("%copy.2 = bf16[8]{0} copy(%a)", 10 * MS, 4 * MS),
           ("%delta_rule_fwd.3 = (bf16[8]{0:T(8,128)(2,1)}) custom-call(%a)", 14 * MS, 12 * MS),
           ("%delta_rule_fwd.4 = (bf16[8]{0:T(8,128)(2,1)}) custom-call(%a)", 26 * MS, 12 * MS),
           ("%delta_rule_bwd.5 = (bf16[8]{0:T(8,128)(2,1)}) custom-call(%a)", 38 * MS, 20 * MS),
           ("%fusion.6 = f32[8]{0} fusion(%a)", 58 * MS, 2 * MS),
           ("%fusion.7 = bf16[8]{0} fusion(%a)", 60 * MS, 40 * MS)]
    trace = xplane.Trace({"/device:TPU:0": xplane.DeviceTrace(
        ops, [("jit_train_step(7)", 0, 100 * MS)])}, [])
    facts = {"kind": "train", "rows": 2, "seq": 8192, "chips": 1,
             "model": _config("olmo-hybrid-7b"),
             "_scope_tables": {"jit_train_step": (table, split_scope)}}
    fresh = lambda **kw: {k: v for k, v in dict(facts, **kw).items() if k != "_train_step_scopes"}
    assert read(fresh(), trace) == pytest.approx(100 * 44 / 50)
    assert read(fresh(), None) is None
    assert read(fresh(_scope_tables={"jit_train_step": None}), trace) is None


def test_the_step_recorded_before_the_kernels_reads_nought(tmp_path):
    """``hybrid_scoped.*`` is PR 28's step: XLA's fusions under the core
    (its ``einsum``s' equations are scopes below it), and no kernel."""
    facts, trace = _recorded(tmp_path, "hybrid_scoped", "olmo-hybrid-7b", 2, 8192)
    assert read(facts, trace) == 0.0


def test_a_step_without_the_scope_reads_nothing(tmp_path):
    facts, trace = _recorded(tmp_path, "train_scoped", "gpt2-large", 16, 1024)
    assert read(facts, trace) is None
