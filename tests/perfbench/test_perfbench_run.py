"""The harness end to end on the CPU at toy size: ``run.py`` refuses to print
a result without a chip; with the look for a chip switched off (tests only) a
sound run is ``correct``, writes no CPU number under a device metric's name,
and a timed path broken underneath comes out not correct, once for each fault
a cell can have."""
import json
import os
import subprocess
import sys

import pytest

import _paths
import run as runner

SEED = 2**31 + 4242


def _run(cell, trace=False, seconds=1.5):
    return runner.run_cell(cell, SEED, seconds, trace, require_tpu=False,
                           bench_path=_paths.TOY_BENCHMARK)


def test_run_py_refuses_to_print_a_result_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(_paths.PERFBENCH, "run.py"), "--workload",
         "gpt2-large.train-b16", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=_paths.ROOT, timeout=300)
    assert proc.returncode == 3
    assert "does not fall back" in proc.stderr
    for line in proc.stdout.splitlines():
        assert "correct" not in json.loads(line)               # facts only, no result


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(_paths.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(_paths.ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(_paths.PERFBENCH, "traffic", w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(_paths.PERFBENCH, "limits", w["name"] + ".json"))
        assert len(w["why"]) <= 200
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(_paths.PERFBENCH, "metrics", m["name"] + ".py"))
        # every cell of a per-layer metric reports the end-to-end metric it moves
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells and w in moved.get("workloads", cells)


@pytest.fixture(scope="module")
def sound_train():
    return _run("toy.train-toy")


def test_toy_train_cell_is_correct_and_reports_its_end_to_end_metrics(sound_train):
    r = sound_train
    assert r["correct"] is True, r["compared"]
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compared"]["compiles_in_window"]["value"] == 0


def test_a_traced_cpu_run_writes_no_number_under_a_device_metrics_name():
    r = _run("toy.train-toy", trace=True)
    assert r["correct"] is True
    assert r["metrics"] == {}            # MFU, rooflines and idle share need the chip
    assert r["device"]["busy_s"] is None and r["device"]["platform"] == "cpu"


def _break_step(monkeypatch, wrap):
    import bench

    real = bench.train_step_fn

    def broken(cfg, mesh):
        step, opt = real(cfg, mesh)
        return wrap(step), opt

    monkeypatch.setattr(bench, "train_step_fn", broken)


def test_fault_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    def wrap(step):
        def lazy(params, opt_state, tok, tgt):
            import jax

            keep = jax.tree.map(lambda x: x.copy(), (params, opt_state))
            _, _, loss = step(params, opt_state, tok, tgt)
            return keep[0], keep[1], loss
        return lazy

    _break_step(monkeypatch, wrap)
    r = _run("toy.train-toy")
    assert r["correct"] is False
    # nothing moved where the reference moves every leaf: the gap reads 1,
    # and the optimizer's state holds no gradient: its error reads 1 too
    assert r["compared"]["grad_error"]["value"] == pytest.approx(1.0)
    assert r["compared"]["update_norm_gap"]["value"] == pytest.approx(1.0)
    assert not r["compared"]["update_norm_gap"]["ok"]


def test_fault_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    def wrap(step):
        def half(params, opt_state, tok, tgt):
            import jax.numpy as jnp

            n = tok.shape[0] // 2     # the mean is taken over the first half alone
            return step(params, opt_state, jnp.concatenate([tok[:n], tok[:n]]),
                        jnp.concatenate([tgt[:n], tgt[:n]]))
        return half

    _break_step(monkeypatch, wrap)
    r = _run("toy.train-toy")
    assert r["correct"] is False
    assert not r["compared"]["grad_norm_gap"]["ok"]


@pytest.mark.parametrize("cell,metrics", [
    ("toy.serve-toy-open", {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}),
    ("toy.serve-toy-closed", {"tpot_p95_ms", "serve_tokens_per_s", "setup_s"}),
])
def test_toy_serve_cells_are_correct(cell, metrics):
    r = _run(cell, seconds=2.0)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == metrics
    assert r["compared"]["served_logit_gap_max"]["tokens_compared"] > 10
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(v["value"] > 0 for v in r["metrics"].values())


def test_fault_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    import apex_tpu.serve.engine as engine

    real = engine.sample

    def altered(logits, keys, positions, cfg):
        return (real(logits, keys, positions, cfg) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample", altered)
    r = _run("toy.serve-toy-open", seconds=2.0)
    assert r["correct"] is False
    assert not r["compared"]["served_logit_gap_max"]["ok"]


def test_training_control_in_fp8_comes_out_not_correct_through_the_comparison(sound_train):
    """The control at a size a test can hold, through the harness's own
    comparison and against the toy cell's limits: the reference computed on
    float8's grid, put in the program's place, reads three times the sound
    program's gradient error and more and is not correct; on int8's grid (as
    careful as bfloat16 itself) it passes, which PERF.md lists under what
    ``correct`` cannot see; half of the batch left out is not correct."""
    import jax.numpy as jnp

    import reference
    import traffic
    import weights

    with open(os.path.join(_paths.DATA, "perfbench", "configs", "toy.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(_paths.DATA, "perfbench", "limits", "toy.train-toy.json")) as f:
        limits = json.load(f)["limits"]
    train = runner._module(os.path.join(_paths.PERFBENCH, "kinds", "train.py"), "kind_train")
    make = lambda: weights.make_params(cfg, SEED)
    batches = [tuple(map(jnp.asarray, traffic.train_batch(SEED, i, 4, 32, cfg["vocab_size"])))
               for i in (1, 2, 3)]
    ref = reference.train_reference(make, batches, cfg["train"], cfg["n_head"], 1e-5, 2)
    sound = sound_train["compared"]["grad_error"]["value"]
    verdict = lambda seen: {n["name"]: n for n in train.compare(seen, ref, limits)}
    fp8 = verdict(reference.train_reference(make, batches, cfg["train"], cfg["n_head"], 1e-5, 2,
                                            quant="fp8"))
    assert not fp8["grad_error"]["ok"] and fp8["grad_error"]["value"] >= 3 * sound
    int8 = verdict(reference.train_reference(make, batches, cfg["train"], cfg["n_head"], 1e-5, 2,
                                             quant="int8"))
    assert all(n["ok"] for n in int8.values())
    half = verdict(reference.train_reference(make, batches, cfg["train"], cfg["n_head"], 1e-5, 2,
                                             rows=slice(0, 2)))
    assert not half["grad_error"]["ok"] and half["grad_error"]["value"] > 0.5


def test_serving_control_in_fp8_comes_out_not_correct_through_the_comparison():
    """The control at a size a test can hold, through the harness's own
    comparison and against the toy cell's limit: a sound run's served tokens
    pass; on the same prompts and positions the token that float8's
    arithmetic puts first lies further below the float32 reference's best
    than the limit allows, so the control is not correct."""
    import time

    import harness
    import reference
    import weights

    data = os.path.join(_paths.DATA, "perfbench")
    load = lambda *parts: json.load(open(os.path.join(data, *parts)))
    cfg = load("configs", "toy.json")
    limits = load("limits", "toy.serve-toy-open.json")["limits"]
    serve = runner._module(os.path.join(_paths.PERFBENCH, "kinds", "serve.py"), "kind_serve")
    ctx = harness.Context(cell="toy.serve-toy-open", config=cfg,
                          mix=load("traffic", "serve-toy-open.json"), chips=1, seed=SEED,
                          seconds=2.0, trace=False, t_process_start=time.perf_counter(),
                          require_tpu=False)
    harness.setup_jax(ctx)
    out = serve.run(ctx, limits)
    assert all(n["ok"] for n in out["numbers"]), out["numbers"]
    # the harness's own count of the window's output tokens is the engine's
    assert out["facts"]["tokens_in_window"] == out["facts"]["engine_generated_tokens"] > 100
    sample = out["check"]["sample"]
    params = weights.make_params(cfg, SEED)
    sound = float(out["check"]["gaps"].max())
    for grid, fails in (("fp8", True), ("int8", False)):
        low = reference.served_token_gaps(params, sample, cfg["n_head"], 1e-5, 128, control=grid)
        assert low.shape == out["check"]["gaps"].shape and (low >= 0).all()
        numbers = serve.compare(low, len(sample), limits, 0)
        assert all(n["ok"] for n in numbers) is not fails, (grid, numbers)
        if fails:
            assert numbers[0]["value"] >= 3 * sound
    # nothing finished, nothing compared: not correct either
    import numpy as np
    assert not serve.compare(np.zeros((0,)), 0, limits, 0)[0]["ok"]
