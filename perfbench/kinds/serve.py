"""A serving cell: the program's continuous-batching engine over its paged
KV pool, driven through ``submit()`` and ``step()`` by an open or a closed
loop.

The harness keeps its own clock. A request's latency runs from the instant it
was *due* (open loop) to the return of the ``engine.step()`` that produced
its first token; the engine's own ``first_token`` / ``retired`` events (its
public ``EventLog``) only say *which* requests a step served. After the window
has closed, ``correct`` runs the plain reference once over a sample of the
finished requests (prompt + served tokens) and reads how far each served
token's logit lies below the reference's best.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

import counts
import reference
import traffic
import weights
from harness import (Context, TraceWindow, compared, memory_by_device,
                     memory_peak_bytes, percentile, span)

DRAIN_LIMIT_S = 60.0     # a due request's first token is waited for this long
TRACE_S = 4.0            # length of the profiler's window in a traced run


class _Book:
    """What the harness knows of each request, on its own clock."""

    def __init__(self):
        self.req: Dict[str, traffic.Req] = {}
        self.submit_s: Dict[str, float] = {}
        self.first_s: Dict[str, float] = {}
        self.last_s: Dict[str, float] = {}
        self.served: Dict[str, List[int]] = {}
        self.queue_ms: List[float] = []
        self._new_first: List[str] = []
        self._new_done: List[str] = []

    # the engine's event tap and retirement hook: they only note *who*
    def on_event(self, rec) -> None:
        ev = rec.get("event")
        if ev == "first_token":
            self._new_first.append(rec["uid"])
        elif ev == "admitted":
            self.queue_ms.append(float(rec.get("queue_ms", 0.0)))

    def on_retire(self, uid: str, tokens: List[int]) -> None:
        self.served[uid] = list(tokens)
        self._new_done.append(uid)

    def reset(self) -> None:
        """Forget the warm-up's requests (the engine keeps this object's tap)."""
        self.__init__()

    def start_s(self, uid: str, open_loop: bool) -> float:
        """Where a request's latency starts: the instant it was *due* in an
        open loop, its submission in a closed one."""
        return self.req[uid].due_s if open_loop else self.submit_s[uid]

    def stamp(self, now_s: float):
        """Give the requests the last step served its return instant.
        Returns (those that got their first token, those that finished)."""
        first, done = self._new_first, self._new_done
        for uid in first:
            self.first_s[uid] = now_s
        for uid in done:
            self.last_s[uid] = now_s
        self._new_first, self._new_done = [], []
        return first, done


def _engine(ctx: Context, book: _Book):
    import jax.numpy as jnp

    from apex_tpu.monitor.events import EventLog
    from apex_tpu.serve import InferenceEngine, ServeConfig
    from apex_tpu.transformer.testing import GPTConfig

    cfg, cap = ctx.config, ctx.config["serve"]
    gpt = GPTConfig(vocab_size=cfg["assumed"]["padded_vocab_size"],
                    max_seq=cfg["n_positions"], hidden=cfg["n_embd"],
                    num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
                    dtype=jnp.dtype(cfg["assumed"]["param_dtype"]))
    params = weights.make_params(cfg, ctx.seed)
    events = EventLog()
    events.tap(book.on_event)
    engine = InferenceEngine(
        params, gpt,
        ServeConfig(num_slots=int(cap["num_slots"]), max_context=int(cap["max_context"])),
        events=events, retain_streams=False, on_retire=book.on_retire)
    return engine


def _submit(engine, book: _Book, req: traffic.Req, now_s: float) -> None:
    from apex_tpu.serve import Request

    book.req[req.uid] = req
    book.submit_s[req.uid] = now_s
    engine.submit(Request(uid=req.uid, tokens=req.tokens,
                          max_new_tokens=req.max_new_tokens))


def _warm_up(ctx: Context, engine, book: _Book) -> None:
    """Every program the window drives: two short requests (chunk prefill,
    decode, and the slice of a slot's row, whose index is an operand and not
    a constant), and a prompt met twice, whole blocks long, for the
    copy-on-write program. A program met first inside the window would show
    in ``compiles_in_window``."""
    vocab = ctx.config["vocab_size"]
    rng = np.random.default_rng([int(ctx.seed), 3])
    block = engine.serve_cfg.block_size
    twice = rng.integers(0, vocab, size=2 * block).tolist()
    reqs = [traffic.Req(f"warm{i:03d}", 0.0, rng.integers(0, vocab, size=8).tolist(), 2)
            for i in range(2)]
    reqs += [traffic.Req("warm-cow-a", 0.0, twice, 2)]
    for r in reqs:
        _submit(engine, book, r, 0.0)
    while engine.step():
        pass
    _submit(engine, book, traffic.Req("warm-cow-b", 0.0, twice, 2), 0.0)
    while engine.step():
        pass
    book.reset()


def run(ctx: Context, limits) -> Dict[str, Any]:
    mix, cfg = ctx.mix, ctx.config
    book = _Book()
    engine = _engine(ctx, book)
    ctx.info(phase="engine built", decode_kernel=engine.decode_kernel,
             num_slots=engine.serve_cfg.num_slots,
             prefill_chunk=engine.serve_cfg.prefill_chunk,
             block_size=engine.serve_cfg.block_size)
    _warm_up(ctx, engine, book)
    open_loop = mix["loop"] == "open"
    max_ctx = int(cfg["serve"]["max_context"])
    if open_loop:
        plan = traffic.open_loop(mix, ctx.seconds, ctx.seed, cfg["vocab_size"], max_ctx)
        ctx.info(phase="traffic", **traffic.describe(plan))
    else:
        stream = traffic.closed_loop(mix, ctx.seed, cfg["vocab_size"], max_ctx)
    compiles_warm = dict(engine.compile_counts())
    setup_s = time.perf_counter() - ctx.t_process_start
    ctx.info(phase="set-up done", setup_s=setup_s, compile_counts=compiles_warm,
             cache_hits=ctx.counters.cache_hits, cache_misses=ctx.counters.cache_misses)

    # -- the measured window -----------------------------------------------------
    tracer = TraceWindow(ctx) if ctx.trace else None
    trace_from = 0.35 * ctx.seconds
    late_ms: List[float] = []
    occupancy: List[float] = []
    ctx_sums: List[tuple] = []              # per step: (return instant, Σ contexts read, slots decoding)
    live: Dict[str, int] = {}               # uid -> tokens generated so far
    live_at_close: Dict[str, int] = {}
    engine_tokens_at_close = None
    trace_started = trace_stopped = float("inf")
    steps = 0
    nxt = 0
    ctx.counters.mark()
    tokens_0 = engine.stats()["generated_tokens"]
    t0 = time.perf_counter()
    now = 0.0
    if not open_loop:
        for _ in range(int(mix["clients"])):
            _submit(engine, book, next(stream), 0.0)

    def send_due(until_s: float, at_s: float) -> None:
        nonlocal nxt
        while nxt < len(plan) and plan[nxt].due_s <= until_s:
            late_ms.append((at_s - plan[nxt].due_s) * 1e3)
            _submit(engine, book, plan[nxt], at_s)
            nxt += 1

    closed = False
    while True:
        now = time.perf_counter() - t0
        if not closed and now >= ctx.seconds:
            closed = True
            engine_tokens_at_close = engine.stats()["generated_tokens"] - tokens_0
            window_s = now
            live_at_close = dict(live)
            if tracer and tracer.active:
                tracer.stop()
                trace_stopped = now
            if not open_loop:
                break
            send_due(ctx.seconds, now)      # due before the close, not yet sent
        if closed and (all(u in book.first_s for u in book.submit_s)
                       or now - ctx.seconds > DRAIN_LIMIT_S):
            break
        if open_loop and not closed:
            with span("hb.submit"):
                send_due(now, now)
        if tracer and not closed:
            if not tracer.done and not tracer.active and now >= trace_from:
                tracer.start()
                trace_started = time.perf_counter() - t0
            elif tracer.active and now >= trace_started + TRACE_S:
                tracer.stop()
                trace_stopped = now
        with span("hb.engine_step"):
            progressed = engine.step()
        now = time.perf_counter() - t0
        if not progressed:
            if open_loop and not closed and nxt < len(plan):
                with span("hb.wait_for_arrival"):
                    time.sleep(max(0.0, min(plan[nxt].due_s, ctx.seconds) - now))
            continue
        steps += 1
        # a step gives every request past its first token one more token;
        # the step that prefills a prompt's last chunk gives it its first
        # token and, in the decode that follows in the same step, its second
        for uid in live:
            live[uid] += 1
        first, done = book.stamp(now)
        for uid in first:
            live[uid] = 2
        if not closed:
            occupancy.append(engine.occupancy())
            # a slot that now holds g tokens read p + g - 2 cached positions
            ctx_sums.append((now, sum(len(book.req[u].tokens) + g - 2
                                      for u, g in live.items()), len(live)))
        for uid in done:
            live.pop(uid, None)
            if not open_loop and not closed:
                with span("hb.submit"):
                    _submit(engine, book, next(stream), now)
    ctx.counters.close()
    mem_peak = memory_peak_bytes(ctx)
    compiles_after = dict(engine.compile_counts())

    # -- what the window's requests saw --------------------------------------------
    due = list(book.req)
    ttft_ms, tpot_ms = latencies(book, open_loop, window_s)
    # open loop: a due request with no first token a minute past the close
    # has failed. Closed loop: the requests in flight at the close are neither
    missing = len(due) - len(ttft_ms) if open_loop else 0
    finished_in_window = [u for u in book.last_s if book.last_s[u] <= window_s]
    # output tokens of the window by the harness's own count: the tokens it
    # was handed for each finished request, and one a step since its first
    # token for each request in flight at the close; the engine's counter is
    # printed beside it
    tokens_at_close = (sum(len(book.served[u]) for u in finished_in_window)
                       + sum(min(g, book.req[u].max_new_tokens)
                             for u, g in live_at_close.items()))
    # a request that never got its first token misses every limit: it counts
    # as the longest wait there could have been, so it pushes the tail
    ttft_all = ttft_ms + [(ctx.seconds + DRAIN_LIMIT_S) * 1e3] * missing
    ctx.info(phase="window closed", steps=steps, window_s=window_s,
             requests_due=len(due), first_tokens=len(ttft_ms),
             finished_in_window=len(finished_in_window), tokens_in_window=tokens_at_close,
             engine_generated_tokens=engine_tokens_at_close,
             compiles_in_window=ctx.counters.in_window, compile_counts=compiles_after,
             late_p95_ms=percentile(late_ms, 95), peak_bytes_in_use=memory_by_device(ctx))

    # tokens fed in the window, with their contexts, from the harness's own count
    fed_flops = 0.0
    for u, r in book.req.items():
        if u not in book.first_s or book.first_s[u] > window_s:
            continue
        p = len(r.tokens)
        if u in book.last_s and book.last_s[u] <= window_s:
            g = len(book.served[u])
        else:
            g = live_at_close.get(u, 1)
        fed_flops += counts.serve_flops_span(cfg, 1, p + g - 1)

    # -- the program's state is freed, then the reference runs ------------------------
    sample = [(book.req[u].tokens, book.served[u])
              for u in _sample(ctx, book, int(mix["check"]["max_requests"]))]
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    params = weights.make_params(cfg, ctx.seed)
    gaps = reference.served_token_gaps(
        params, sample, cfg["n_head"], float(cfg["layer_norm_epsilon"]), max_ctx)
    del params
    numbers = compare(gaps, len(sample), limits, ctx.counters.in_window)
    ctx.info(phase="reference done", reference_s=time.perf_counter() - t_ref,
             gap_p50=float(np.median(gaps)) if gaps.size else None,
             gap_nonzero=int((gaps > 0).sum()))

    end_to_end = {"setup_s": setup_s,
                  "ttft_p95_ms": percentile(ttft_all, 95),
                  "tpot_p95_ms": percentile(tpot_ms, 95),
                  "serve_tokens_per_s": tokens_at_close / window_s}
    return {
        "attempted": len(due), "failed": missing, "numbers": numbers,
        "memory_peak_bytes": mem_peak, "end_to_end": end_to_end,
        "facts": {"kind": "serve", "model": cfg, "peaks": ctx.peaks, "chips": ctx.chips,
                  "window_s": window_s, "late_ms": late_ms, "queue_ms": book.queue_ms,
                  "occupancy": occupancy, "ctx_sums": ctx_sums,
                  "trace_span": (trace_started, trace_stopped),
                  "ttft_by_due": sorted(
                      (book.start_s(u, open_loop),
                       (book.first_s[u] - book.start_s(u, open_loop)) * 1e3)
                      for u in book.first_s),
                  "drain_s": now - window_s, "fed_flops": fed_flops,
                  "tokens_in_window": tokens_at_close,
                  "engine_generated_tokens": engine_tokens_at_close},
        "tracer": tracer,
        "check": {"sample": sample, "gaps": gaps},
    }


def compare(gaps, n_requests: int, limits, compiles_in_window: int) -> list:
    """The numbers compared, each beside its limit: the widest gap by which a
    served token's reference logit lies below the reference's best (``nan``,
    and so not correct, where the window finished nothing to compare)."""
    numbers = [compared("served_logit_gap_max", float(gaps.max()) if gaps.size else float("nan"),
                        limits["served_logit_gap_max"]),
               compared("compiles_in_window", compiles_in_window, 0)]
    numbers[0]["tokens_compared"] = int(gaps.size)
    numbers[0]["requests_compared"] = n_requests
    return numbers


def latencies(book: _Book, open_loop: bool, window_s: float):
    """(ttft_ms of every request with a first token, tpot_ms of every request
    finished inside the window). Time to first token runs from the instant
    the request was *due* in an open loop (from its submission in a closed
    one) to the return of the step that produced the token."""
    ttft_ms = [(book.first_s[u] - book.start_s(u, open_loop)) * 1e3
               for u in book.req if u in book.first_s]
    tpot_ms = [(book.last_s[u] - book.first_s[u]) * 1e3 / (len(book.served[u]) - 1)
               for u in book.last_s
               if book.last_s[u] <= window_s and len(book.served[u]) > 1]
    return ttft_ms, tpot_ms


def _sample(ctx: Context, book: _Book, k: int) -> List[str]:
    """The finished requests, or where there are more than ``k`` of them,
    ``k`` drawn from the seed with the longest among them."""
    done = sorted(u for u in book.served if u in book.req and len(book.served[u]) > 0)
    if not done:
        return []
    longest = max(done, key=lambda u: (len(book.req[u].tokens) + len(book.served[u]), u))
    rest = [u for u in done if u != longest]
    rng = np.random.default_rng([int(ctx.seed), 4])
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False)) if rest else []
    return [longest] + [str(u) for u in pick]


def readings(ctx: Context, seeds, control_seeds, fault_seeds) -> list:
    """For the limits: on each of ``seeds`` a short window at the cell's own
    load and the widest gap the comparison reads there; on the first few also
    the control's: at each position of the same prompts and served tokens, the
    gap of the token that int8's, and that fp8's, arithmetic puts first."""
    out = []
    for seed in seeds:
        ctx.seed = int(seed)
        ctx.t_process_start = time.perf_counter()
        res = run(ctx, {"served_logit_gap_max": float("inf")})
        gaps = res["check"]["gaps"]
        rec = {"seed": int(seed), "tokens_compared": int(gaps.size),
               "program_gap_max": float(gaps.max()),
               "program_gap_p99": float(np.percentile(gaps, 99)),
               "program_tokens_off_best": int((gaps > 0).sum()),
               "end_to_end": res["end_to_end"], "attempted": res["attempted"],
               "failed": res["failed"], "drain_s": res["facts"]["drain_s"],
               "steps_occupancy_mean": float(np.mean(res["facts"]["occupancy"]))}
        by_due = res["facts"]["ttft_by_due"]
        half = ctx.seconds / 2
        rec["ttft_p50_first_half_ms"] = percentile([t for d, t in by_due if d < half], 50)
        rec["ttft_p50_second_half_ms"] = percentile([t for d, t in by_due if d >= half], 50)
        if seed in control_seeds:
            params = weights.make_params(ctx.config, ctx.seed)
            for grid in ("int8", "fp8"):
                low = reference.served_token_gaps(
                    params, res["check"]["sample"], ctx.config["n_head"],
                    float(ctx.config["layer_norm_epsilon"]),
                    int(ctx.config["serve"]["max_context"]), control=grid)
                rec["control_" + grid] = {
                    "gap_max": float(low.max()),
                    "gap_p99": float(np.percentile(low, 99)),
                    "tokens_off_best": int((low > 0).sum())}
            del params
        ctx.info(**rec)
        out.append(rec)
        gc.collect()
    return out
