"""One general traffic generator, driven by a mix's data file.

A serving mix's file gives the loop (open or closed), the arrival process
(Poisson rate, bursts) or the number of clients, and lognormal prompt and
output lengths with their clips. The *set* of arrival instants and of
(prompt, output) lengths is drawn once from the file's own ``shape_seed``, so
every run offers the same amount of work; the run's ``--seed`` gives it in
another order (which lengths meet which arrival in an open loop; the order
within each wave of ``clients`` requests in a closed one) and draws the
weights and every token id. A training mix's file gives rows and sequence
length; the seed draws the ids of every batch.

Copied in spirit from ``benchmarks/loadgen.py`` (Poisson gaps, bursts that
collapse the next arrivals onto one instant, clipped lognormal lengths), with
what an open loop needs and that one lacks: every request carries the instant
it is *due*, and latency is taken from there.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Mapping, Tuple

import numpy as np


@dataclasses.dataclass
class Req:
    uid: str
    due_s: float            # instant the request is due, from the window's start
    tokens: List[int]
    max_new_tokens: int


def _lognormal_int(rng, spec: Mapping, size: int) -> np.ndarray:
    v = rng.lognormal(mean=np.log(spec["median"]), sigma=spec["sigma"], size=size)
    return np.clip(np.round(v).astype(np.int64), spec["min"], spec["max"])


def _lengths(rng, mix: Mapping, n: int, max_context: int) -> Tuple[np.ndarray, np.ndarray]:
    plens = _lognormal_int(rng, mix["prompt"], n)
    olens = _lognormal_int(rng, mix["output"], n)
    if int((plens + olens).max()) > max_context:
        raise ValueError("mix can draw prompt + output beyond max_context; "
                         "choose traffic on which no operation fails")
    return plens, olens


def _tokens(seed: int, index: int, n: int, vocab: int) -> List[int]:
    return np.random.default_rng([int(seed), 1, index]).integers(
        0, vocab, size=n).tolist()


def open_loop(mix: Mapping, seconds: float, seed: int, vocab: int,
              max_context: int) -> List[Req]:
    """Requests due in ``[0, seconds)``, sorted by due instant."""
    shape = np.random.default_rng(int(mix["shape_seed"]))
    rate = float(mix["rate_rps"])
    if rate <= 0:
        raise ValueError("open loop needs a positive rate_rps")
    n = int(rate * seconds * 1.5) + 64
    arrivals = np.cumsum(shape.exponential(1.0 / rate, size=n))
    plens, olens = _lengths(shape, mix, n, max_context)
    every, size = mix.get("burst_every_s"), int(mix.get("burst_size", 0))
    if every and size:
        t = float(every)
        while t < seconds:
            j = int(np.searchsorted(arrivals, t))
            arrivals[j:j + size] = t
            t += float(every)
        arrivals = np.sort(arrivals)
    n = int(np.searchsorted(arrivals, seconds))
    # the same arrivals and the same n pairs of lengths for every seed; the
    # seed says which pair meets which arrival
    order = np.random.default_rng([int(seed), 5]).permutation(n)
    return [Req(uid=f"r{i:05d}", due_s=float(arrivals[i]),
                tokens=_tokens(seed, i, int(plens[order[i]]), vocab),
                max_new_tokens=int(olens[order[i]]))
            for i in range(n)]


def closed_loop(mix: Mapping, seed: int, vocab: int, max_context: int
                ) -> Iterator[Req]:
    """An endless stream of requests for ``mix["clients"]`` waiting clients:
    the caller takes the next one whenever a client's reply has come, and
    sets ``due_s`` itself. Every seed gets the same lengths wave by wave (a
    wave is ``clients`` requests); the seed orders each wave and draws the
    token ids."""
    shape = np.random.default_rng(int(mix["shape_seed"]))
    wave = int(mix["clients"])
    pool = wave * max(1, 4096 // wave)
    plens, olens = _lengths(shape, mix, pool, max_context)
    order = np.random.default_rng([int(seed), 5]).permuted(
        np.arange(pool).reshape(-1, wave), axis=1).reshape(-1)
    j = 0
    while True:
        i = int(order[j % pool])
        yield Req(uid=f"r{j:05d}", due_s=0.0,
                  tokens=_tokens(seed, j, int(plens[i]), vocab),
                  max_new_tokens=int(olens[i]))
        j += 1


def train_batch(seed: int, step: int, rows: int, seq: int, vocab: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """The (tokens, targets) batch of global step ``step``: ``rows`` rows
    that all differ, targets the next token."""
    x = np.random.default_rng([int(seed), 2, int(step)]).integers(
        0, vocab, size=(rows, seq + 1), dtype=np.int32)
    return x[:, :-1], x[:, 1:]


def describe(reqs: List[Req]) -> Dict[str, float]:
    p = np.array([len(r.tokens) for r in reqs])
    o = np.array([r.max_new_tokens for r in reqs])
    return {"requests": len(reqs), "prompt_tokens": int(p.sum()),
            "output_tokens": int(o.sum()),
            "prompt_p50": float(np.median(p)) if len(p) else 0.0,
            "output_p50": float(np.median(o)) if len(o) else 0.0}
