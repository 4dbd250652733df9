"""The traffic generator: the same seed gives the same requests, every seed
the same set of sizes and arrivals in another order, and latency runs from
the due instant."""
import collections
import json
import os

import numpy as np
import pytest

import _paths
import traffic


def _mix(name):
    with open(os.path.join(_paths.DATA, "perfbench", "traffic", name + ".json")) as f:
        return json.load(f)


VOCAB, MAX_CTX = 250, 128


def _as_tuples(reqs):
    return [(r.uid, r.due_s, tuple(r.tokens), r.max_new_tokens) for r in reqs]


def test_open_loop_same_seed_same_requests():
    mix = _mix("serve-toy-open")
    a = traffic.open_loop(mix, 4.0, 2**31 + 7, VOCAB, MAX_CTX)
    b = traffic.open_loop(mix, 4.0, 2**31 + 7, VOCAB, MAX_CTX)
    assert _as_tuples(a) == _as_tuples(b)
    assert len(a) > 10


def test_open_loop_every_seed_offers_the_same_sizes_and_arrivals_in_another_order():
    mix = _mix("serve-toy-open")
    a = traffic.open_loop(mix, 4.0, 1, VOCAB, MAX_CTX)
    b = traffic.open_loop(mix, 4.0, 987654321012, VOCAB, MAX_CTX)
    sizes = lambda rs: [(len(r.tokens), r.max_new_tokens) for r in rs]
    assert [r.due_s for r in a] == [r.due_s for r in b]      # the same arrivals
    assert sorted(sizes(a)) == sorted(sizes(b))              # the same work
    assert sizes(a) != sizes(b)                              # in another order
    due = np.array([r.due_s for r in a])
    assert (due >= 0).all() and (due < 4.0).all() and (np.diff(due) >= 0).all()
    # ids below the vocabulary, prompts and answers inside the clips
    for r in a:
        assert 0 <= min(r.tokens) and max(r.tokens) < VOCAB
        assert mix["prompt"]["min"] <= len(r.tokens) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new_tokens <= mix["output"]["max"]


def test_open_loop_bursts_put_several_requests_on_one_instant():
    mix = _mix("serve-toy-open")
    reqs = traffic.open_loop(mix, 4.0, 0, VOCAB, MAX_CTX)
    same = collections.Counter(round(r.due_s, 9) for r in reqs)
    assert max(same.values()) == mix["burst_size"]
    assert sum(1 for v in same.values() if v == mix["burst_size"]) >= 6   # 4 s / 0.5 s - edge


def test_closed_loop_same_seed_same_stream_and_every_seed_the_same_waves():
    mix = _mix("serve-toy-closed")
    take = lambda seed, n: [next(s) for s in [traffic.closed_loop(mix, seed, VOCAB, MAX_CTX)] for _ in range(n)]
    a, b, c = take(5, 300), take(5, 300), take(6, 300)
    assert _as_tuples(a) == _as_tuples(b)
    assert _as_tuples(a) != _as_tuples(c)
    sizes = lambda rs: [(len(r.tokens), r.max_new_tokens) for r in rs]
    wave = mix["clients"]
    assert sizes(a) != sizes(c)                  # another order,
    for at in range(0, 300, wave):               # the same lengths wave by wave
        assert sorted(sizes(a[at:at + wave])) == sorted(sizes(c[at:at + wave]))
    for r in a:
        assert mix["prompt"]["min"] <= len(r.tokens) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new_tokens <= mix["output"]["max"]
        assert len(r.tokens) + r.max_new_tokens <= MAX_CTX


def test_a_mix_that_could_overrun_the_context_is_refused():
    mix = dict(_mix("serve-toy-closed"), output={"median": 90, "sigma": 0.5, "min": 12, "max": 100})
    with pytest.raises(ValueError, match="beyond max_context"):
        next(traffic.closed_loop(mix, 0, VOCAB, MAX_CTX))


def test_train_batches_differ_by_row_step_and_seed_and_targets_are_the_next_token():
    tok, tgt = traffic.train_batch(3, 1, 16, 1024, 50257)
    tok2, _ = traffic.train_batch(3, 1, 16, 1024, 50257)
    assert (tok == tok2).all()
    assert tok.shape == tgt.shape == (16, 1024) and tok.dtype == np.int32
    assert (tok[:, 1:] == tgt[:, :-1]).all()
    assert len({row.tobytes() for row in tok}) == 16           # rows that all differ
    assert (traffic.train_batch(3, 2, 16, 1024, 50257)[0] != tok).any()
    assert (traffic.train_batch(4, 1, 16, 1024, 50257)[0] != tok).any()
    assert tok.max() < 50257


def test_latency_runs_from_the_due_instant_not_from_submit():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "kind_serve", os.path.join(_paths.PERFBENCH, "kinds", "serve.py"))
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)
    book = serve._Book()
    book.req["a"] = traffic.Req("a", 1.0, [1, 2, 3], 5)       # due at 1.0 s
    book.submit_s["a"] = 1.2                                   # the generator ran 200 ms late
    book.first_s["a"] = 1.5
    book.last_s["a"] = 2.3
    book.served["a"] = [7, 8, 9, 10, 11]
    ttft, tpot = serve.latencies(book, open_loop=True, window_s=10.0)
    assert ttft == [pytest.approx(500.0)]                      # not 300
    assert tpot == [pytest.approx(200.0)]                      # (2.3 - 1.5) / 4
    ttft, _ = serve.latencies(book, open_loop=False, window_s=10.0)
    assert ttft == [pytest.approx(300.0)]                      # a waiting client: from its send
    _, tpot = serve.latencies(book, open_loop=True, window_s=2.0)
    assert tpot == []                                          # finished after the close
