"""The five set-up readers (``perfbench/metrics/setup_*.py``) on a hand-made
host log, and on the toy train cell's own log run on the CPU."""
import json
import os

import pytest

import _paths
import hostlog
import run as runner
from apex_tpu.monitor import trace
from apex_tpu.monitor.trace import HostLog, HostRecord

READERS = ("setup_compile_s", "setup_lower_s", "setup_steps_s", "setup_step_compiles",
           "setup_cache_misses")
FACTS = {"model": {"name": "toy"}}


def _reader(name):
    return runner._module(os.path.join(_paths.PERFBENCH, "metrics", name + ".py"),
                          "metric_" + name)


def _call(i, start, end):
    return HostRecord("train_step", "span", start, end, None, None, i)


def _path(kind, program, start, end, call=None, cached=None, count=1):
    name = program if kind == "trace" else f"jit({program})"
    return HostRecord(name, kind, start, end, "train_step" if call else None,
                      program, call, count, cached)


HAND_MADE = [
    _path("trace", "init", 0.05, 0.1), _path("lower", "init", 0.1, 0.2),
    _path("compile", "init", 0.2, 0.5, cached=True),
    _path("trace", "train_step", 1.0, 2.0, call=1, count=300),
    _path("lower", "train_step", 2.0, 2.5, call=1),
    _path("compile", "train_step", 2.5, 4.0, call=1, cached=False),
    _call(1, 1.0, 4.2),
    _path("compile", "grad_norms", 4.5, 4.8, cached=False),
    _path("trace", "train_step", 5.0, 5.5, call=2, count=280),
    _path("lower", "train_step", 5.5, 6.0, call=2),
    _path("compile", "train_step", 6.0, 6.9, call=2, cached=True),
    _call(2, 5.0, 7.0), _call(3, 7.5, 7.6), _call(4, 8.0, 8.1),
    # after set-up: the window's steps, the scope table's compile
    _call(5, 8.2, 8.3),
    _path("compile", "train_step", 21.0, 29.0, cached=False),
    HostRecord("scope_table", "span", 20.0, 30.0, None, None, None),
]

WANT = {
    "setup_compile_s": 0.3 + 1.5 + 0.3 + 0.9,
    "setup_lower_s": 0.15 + 1.5 + 1.0,
    # calls 1 to 4: 7.0 s, of which the compile path covers 3.0 + 0.3 + 1.9
    "setup_steps_s": 7.0 - 5.2,
    "setup_step_compiles": 2,
    "setup_cache_misses": 2,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_hand_made_log(name, monkeypatch):
    monkeypatch.setattr(hostlog, "records", lambda: HAND_MADE)
    assert _reader(name).read(FACTS, None) == pytest.approx(WANT[name])


def test_set_up_ends_where_the_steps_fourth_call_starts():
    got = hostlog.setup(HAND_MADE)
    assert (got["first"], got["end"]) == (1.0, 8.0)
    assert hostlog.CHECK_STEPS == runner._module(
        os.path.join(_paths.PERFBENCH, "kinds", "train.py"), "kind_train_check").CHECK_STEPS
    assert max(r.end for r in got["records"]) < 8.0


def test_the_table_by_program_counts_what_the_reader_counts(monkeypatch, capsys):
    monkeypatch.setattr(hostlog, "records", lambda: HAND_MADE)
    _reader("setup_compile_s").read(FACTS, None)
    line = json.loads(capsys.readouterr().out)
    step = next(row for row in line["setup_by_program"] if row[0] == "train_step")
    assert step == ["train_step", 1.5, 1.0, pytest.approx(2.4), 1, 1, 580]
    assert step[4] + step[5] == WANT["setup_step_compiles"]
    assert line["setup_records"] == 13


@pytest.mark.parametrize("log", [
    None,                                           # a program with no host log
    [r for r in HAND_MADE if r.call != 4],          # the window never started
])
def test_readers_return_nothing_without_a_log_to_read(log, monkeypatch):
    monkeypatch.setattr(hostlog, "records", lambda: log)
    for name in READERS:
        assert _reader(name).read(FACTS, None) is None


@pytest.fixture(scope="module")
def toy_run():
    """The toy train cell with a log of its own, and the readers on it."""
    before = trace._LOG
    trace._LOG = HostLog()
    try:
        r = runner.run_cell("toy.train-toy", 2**31 + 4343, 1.5, False, require_tpu=False,
                            bench_path=_paths.TOY_BENCHMARK)
        return r, {n: _reader(n).read(FACTS, None) for n in READERS}, hostlog.setup()
    finally:
        trace._LOG = before


def test_the_toy_cells_set_up_compiles_the_step_twice(toy_run):
    _, read, _ = toy_run
    # the optimizer state comes from ``jax.jit(opt.init)`` unplaced, so the
    # second call meets other shardings (ROADMAP S7a)
    assert read["setup_step_compiles"] == 2
    assert read["setup_compile_s"] > 0 and read["setup_lower_s"] > 0
    assert read["setup_steps_s"] > 0


def test_the_log_covers_no_more_than_the_runs_setup_s(toy_run):
    r, _, got = toy_run
    covered = hostlog.seconds(got["records"], ("span",) + hostlog.COMPILE_PATH)
    assert covered <= r["metrics"]["setup_s"]["value"]
