"""The benchmark takes a new configuration, cell and per-layer metric as new
files and appended entries alone. Each family's structural checks
(``check_benchmark`` in its ``test_perfbench_*.py``) and the harness's check
of the files named hold on ``BENCHMARK.json`` as committed and on a copy with
a seventh configuration, cell and metric appended; and no configuration is of
two families by the readers' predicates (``is_*`` in ``perfbench/scopes_*.py``)."""
import copy
import glob
import importlib
import json
import os

import pytest

import _paths
import run as runner
import test_perfbench_run

TOY = "toy.train-toy"
TOY_METRIC = "toy_step_share_pct"


def _load(path):
    with open(path) as f:
        return json.load(f)


def _family_checks():
    checks = {}
    for path in sorted(glob.glob(os.path.join(_paths.TESTS, "test_perfbench_*.py"))):
        name = os.path.basename(path)[:-3]
        check = getattr(importlib.import_module(name), "check_benchmark", None)
        if check is not None:
            checks[name] = check
    return checks


def _appended(bench, metrics_root):
    """``bench`` with the toy train cell's configuration and cell appended, a
    per-layer metric that lists that cell alone (its reader written under
    ``metrics_root``), and the cell added to ``train_tokens_per_s`` and to
    every per-layer metric that lists every accepted cell, as a PR that adds
    a model would do."""
    toy = _load(_paths.TOY_BENCHMARK)
    new = copy.deepcopy(bench)
    accepted = [w["name"] for w in new["workloads"]]
    new["configs"].append(next(c for c in toy["configs"] if c["name"] == "toy"))
    new["workloads"].append(next(w for w in toy["workloads"] if w["name"] == TOY))
    next(m for m in new["end_to_end"] if m["name"] == "train_tokens_per_s")["workloads"].append(TOY)
    for m in new["per_layer"]:
        if set(accepted) <= set(m["workloads"]):
            m["workloads"].append(TOY)
    new["per_layer"].append({"name": TOY_METRIC, "unit": "%", "better": "lower",
                             "source": "device_trace", "layer": "train step",
                             "moves": "train_tokens_per_s", "workloads": [TOY]})
    reader = os.path.join(metrics_root, "perfbench", "metrics", TOY_METRIC + ".py")
    os.makedirs(os.path.dirname(reader))
    with open(reader, "w") as f:
        f.write("def read(facts, trace):\n    return None\n")
    return new


def test_each_family_keeps_its_checks_in_its_own_test_file():
    assert {"test_perfbench_sdar", "test_perfbench_dsv2", "test_perfbench_setup"} <= set(
        _family_checks())


@pytest.mark.parametrize("appended", [False, True], ids=["as-committed", "seventh-appended"])
def test_the_checks_hold_on_the_benchmark_and_on_a_copy_with_a_cell_appended(tmp_path, appended):
    bench = _load(os.path.join(_paths.ROOT, "BENCHMARK.json"))
    if appended:
        before, bench = bench, _appended(bench, str(tmp_path))
        # appends alone: every entry that was there is there, in its place
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            assert len(bench[key]) >= len(before[key])
            for old, new in zip(before[key], bench[key]):
                assert new in (old, dict(old, workloads=old.get("workloads", []) + [TOY]))
        shared = {m["name"] for m in before["per_layer"]
                  if {w["name"] for w in before["workloads"]} <= set(m["workloads"])}
        traced = {m["name"] for m in runner.metrics_for(bench["per_layer"], TOY)}
        assert traced == shared | {TOY_METRIC}
    for check in _family_checks().values():
        check(bench)
    test_perfbench_run.check_names_files_that_exist(
        bench, roots=(_paths.ROOT, _paths.DATA, str(tmp_path)))


def _predicates():
    found = {}
    for path in sorted(glob.glob(os.path.join(_paths.PERFBENCH, "scopes_*.py"))):
        mod = runner._module(path, os.path.basename(path)[:-3])
        found.update({f"{mod.__name__}.{a}": getattr(mod, a) for a in dir(mod)
                      if a.startswith("is_") and getattr(mod, a).__module__ == mod.__name__})
    return found


CONFIGS = sorted(glob.glob(os.path.join(_paths.PERFBENCH, "configs", "*.json"))
                 + glob.glob(os.path.join(_paths.DATA, "perfbench", "configs", "*.json")))


@pytest.mark.parametrize("path", CONFIGS, ids=[os.path.basename(p)[:-5] for p in CONFIGS])
def test_a_configuration_satisfies_at_most_one_family_predicate(path):
    preds = _predicates()
    assert {"scopes_sdar.is_sdar", "scopes_dsv2.is_dsv2", "scopes_hybrid.is_hybrid"} <= set(preds)
    facts = {"kind": "train", "model": _load(path)}
    held = [name for name, pred in preds.items() if pred(facts)]
    assert len(held) <= 1, held
    # a family's own configuration, real or toy, is of that family
    model_type = facts["model"].get("model_type")
    if model_type in ("sdar_moe", "deepseek_v2", "olmo_hybrid"):
        assert len(held) == 1, model_type
