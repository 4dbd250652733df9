#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py                  # the chip run; needs a TPU
    python chip_smoke.py --cpu-rehearsal  # tiny sizes on the CPU, on request

One process drives the two main paths once, through the entry points a user
calls, at GPT-2-124M's full width (vocab 50304, seq 1024, hidden 768, 12
layers, 12 heads, bf16, random weights from a seed):

* **kernels** — each main-path Pallas kernel against its reference
  (``benchmarks/smoke_tpu.py``'s rows, imported);
* **train** — ``apex_tpu.train.train_step_fn``: ``build_mesh`` ->
  ``shard_map(gpt_loss)`` -> ``value_and_grad`` -> ``FusedAdam`` with donated
  params, batch 32 x 1024, remat full; the compiled step must contain the
  Mosaic kernels its dispatch sites should pick, and seven steps must give a
  finite, falling loss;
* **serve** — ``InferenceEngine(params, cfg, ServeConfig())``: eight greedy
  requests (prompts of 5 to 640 tokens, 32 new tokens each) run to
  completion on the fused decode block, then the same requests with
  ``megakernel="off"`` on the Pallas paged kernel; one compilation per
  program. In bf16 the two sets of streams may part at a near-tie (at most
  half may); in fp32 under matmul precision "highest" — the CPU parity
  test's oracle, on compiled kernels — they must be equal;
* **four chips** — only when JAX reports four or more: the same train step
  under dp=4 and tp=2 x dp=2 (every device of the mesh must hold memory) and
  ``__graft_entry__.dryrun_multichip(4)``, in this process.

With no arguments this is the chip run: it exits non-zero — and prints no
result — when the default backend is not ``tpu``, when anything raises (no
phase is wrapped in a catch), or when any check fails. The last line of
standard output is then ``{"ok": true, "device": {...}}`` with the device as
JAX reports it. Timings are printed as information, never as a metric.

The CPU rehearsal is never inferred from finding no chip: it is asked for,
says so in its first line, runs toy sizes through the Pallas interpreter to
exercise this script's control flow, and proves nothing about the chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import math
import os
import sys
import time

import jax


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run is sized to: the model's widths, the train batch and
    the serve prompts."""

    model: dict
    batch: int
    seq: int
    prompt_lens: tuple
    max_new: int


CHIP = Sizes(
    model={},  # GPTConfig's defaults ARE the GPT-2-124M widths
    batch=32, seq=1024,
    prompt_lens=(5, 17, 40, 9, 33, 128, 300, 640), max_new=32)
REHEARSAL = Sizes(
    model=dict(vocab_size=512, hidden=128, num_layers=2, num_heads=4),
    batch=4, seq=128,
    prompt_lens=(5, 17, 40, 9, 33, 12, 60, 100), max_new=8)

# the Mosaic kernels the compiled train step must contain: flash attention
# forward and both backward kernels, the fused LM-head loss forward and both
# backward kernels, Pallas LayerNorm (``ln_pallas`` auto picks it at 32768 x
# 768) and FusedAdam's fused tail
TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "lm_head_fwd",
                 "lm_head_bwd_dx", "lm_head_bwd_dw", "layer_norm_fwd",
                 "layer_norm_bwd", "adam_tail")

_FAILURES: list = []
_CACHE = {"hits": 0, "misses": 0}


def check(ok: bool, what: str) -> None:
    """Record a failed check and keep going, so one run reports every
    failure; ``main`` exits non-zero if any was recorded."""
    if not ok:
        _FAILURES.append(what)
        print(f"  CHECK FAILED: {what}", flush=True)


def _on_jax_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _CACHE["misses"] += 1


class Phase:
    """Prints one line per phase: seconds, and the compile cache's traffic
    while it ran. Not a guard — exceptions pass straight through."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.cache0 = dict(_CACHE)
        print(f"[{self.name}] ...", flush=True)
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            print(f"[{self.name}] done in "
                  f"{time.perf_counter() - self.t0:.1f}s (compile cache: "
                  f"{_CACHE['hits'] - self.cache0['hits']} hits, "
                  f"{_CACHE['misses'] - self.cache0['misses']} misses)",
                  flush=True)
        return False


def _version(pkg: str) -> str:
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def _memory_line() -> str:
    parts = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        parts.append(f"{d.id}: {stats.get('bytes_in_use', 0) / 2**30:.2f} GiB "
                     f"in use, peak "
                     f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f}")
    return "; ".join(parts)


# ---------------------------------------------------------------------------
# phases


def kernel_phase(rehearsal: bool) -> None:
    from benchmarks.smoke_tpu import MAIN_PATH, run_row

    with Phase("kernels"):
        for name in MAIN_PATH:
            row = run_row(name, small=rehearsal)
            print(f"  {name}: max_err {row['max_err']:.3e} (tol "
                  f"{row['tol']:g}) in {row['seconds']}s", flush=True)
            check(row["ok"], f"kernel row {name}: {row}")


def _placed_train_step(cfg, batch: int, seq: int, *, dp: int, tp: int):
    """``(train_step, params, opt_state, tok, tgt)`` on a dp x tp mesh over
    the first ``dp * tp`` devices, every input placed by the mesh's
    shardings (parameters per the model's own ``param_specs()``, the batch
    split over ``dp``), so that no device is left empty."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.train import train_step_fn

    mesh = build_mesh(tp=tp, pp=1, sp=1, dp=dp,
                      devices=jax.devices()[:dp * tp])
    step, opt = train_step_fn(cfg, mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             cfg.param_specs())
    data = NamedSharding(mesh, P("dp"))
    params = jax.device_put(cfg.init_params(jax.random.PRNGKey(0)), shardings)
    tok = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                             cfg.vocab_size)
    return (step, params, opt.init(params), jax.device_put(tok, data),
            jax.device_put(jnp.roll(tok, -1, axis=1), data))


def _train_leg(sizes: Sizes, rehearsal: bool, *, dp: int, tp: int,
               steps: int) -> None:
    from apex_tpu.ops._pallas_util import mosaic_calls
    from apex_tpu.transformer.testing import GPTConfig

    cfg = GPTConfig(max_seq=sizes.seq, remat=True, remat_policy="full",
                    **sizes.model)
    t0 = time.perf_counter()
    step, params, opt_state, tok, tgt = _placed_train_step(
        cfg, sizes.batch, sizes.seq, dp=dp, tp=tp)
    compiled = step.lower(params, opt_state, tok, tgt).compile()
    kernels = mosaic_calls(compiled.as_text())
    print(f"  dp={dp} tp={tp}: set-up + compile "
          f"{time.perf_counter() - t0:.1f}s; Mosaic calls in the compiled "
          f"step: {dict(sorted(kernels.items()))}", flush=True)
    if not rehearsal:  # the CPU picks the references by design
        missing = [k for k in TRAIN_KERNELS if not kernels[k]]
        check(not missing, f"train step dp={dp} tp={tp}: dispatch took the "
                           f"reference for {missing}")
    t0 = time.perf_counter()
    losses = []
    for _ in range(steps):
        # the executable just inspected IS the jitted step's (same
        # donation, same shardings); calling it skips a second compile
        params, opt_state, loss = compiled(params, opt_state, tok, tgt)
        losses.append(float(loss))  # the host read is the execution fence
    print(f"  dp={dp} tp={tp}: {steps} steps of {sizes.batch} x {sizes.seq} "
          f"in {time.perf_counter() - t0:.1f}s, loss "
          f"{' '.join(f'{v:.4f}' for v in losses)}", flush=True)
    check(all(math.isfinite(v) for v in losses),
          f"train dp={dp} tp={tp}: loss not finite: {losses}")
    # random weights predict nothing: the first loss sits at ln(vocab)
    check(abs(losses[0] - math.log(cfg.vocab_size)) < 1.0,
          f"train dp={dp} tp={tp}: first loss {losses[0]} is not near "
          f"ln(vocab) = {math.log(cfg.vocab_size):.2f}")
    check(losses[-1] < losses[0],
          f"train dp={dp} tp={tp}: loss did not fall: {losses}")
    n_used = sum(1 for d in jax.devices()[:dp * tp]
                 if (d.memory_stats() or {}).get("bytes_in_use", 0) > 0)
    check(rehearsal or n_used == dp * tp,
          f"train dp={dp} tp={tp}: only {n_used} of {dp * tp} devices hold "
          f"memory — a sharding is missing")


def train_phase(sizes: Sizes, rehearsal: bool) -> None:
    with Phase("train"):
        # two warm-up steps, then five more
        _train_leg(sizes, rehearsal, dp=1, tp=1, steps=7)
        print(f"  device memory: {_memory_line()}", flush=True)


def serve_phase(sizes: Sizes, rehearsal: bool) -> None:
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.serve import InferenceEngine, Request, ServeConfig
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

    rng = np.random.default_rng(0)
    vocab = GPTConfig(**sizes.model).vocab_size
    prompts = [rng.integers(0, vocab, size=n).tolist()
               for n in sizes.prompt_lens]

    def run(cfg, params, megakernel: str, want_kernel: str):
        t0 = time.perf_counter()
        eng = InferenceEngine(params, cfg, ServeConfig(megakernel=megakernel))
        reqs = [Request(f"r{i}", list(p), max_new_tokens=sizes.max_new)
                for i, p in enumerate(prompts)]
        out = eng.run(reqs)
        stats, counts = eng.stats(), eng.compile_counts()
        tag = f"serve {jnp.dtype(cfg.dtype).name} megakernel={megakernel!r}"
        print(f"  {tag}: {len(out)} requests, "
              f"{sum(len(v) for v in out.values())} tokens in "
              f"{time.perf_counter() - t0:.1f}s incl. compile; decode_kernel="
              f"{stats['decode_kernel']}, "
              f"{stats['prefill']['chunks_run']} prefill chunks, "
              f"{stats['speculative']['decode_steps']} decode steps, "
              f"compilations {counts}", flush=True)
        check(stats["decode_kernel"] == want_kernel,
              f"{tag}: decode_kernel is {stats['decode_kernel']!r}, "
              f"expected {want_kernel!r}")
        check(sorted(out) == sorted(r.uid for r in reqs),
              f"{tag}: finished {sorted(out)}")
        check(all(len(v) == sizes.max_new for v in out.values()),
              f"{tag}: stream lengths {[len(v) for v in out.values()]}")
        check(all(0 <= t < cfg.vocab_size for v in out.values() for t in v),
              f"{tag}: token id out of range")
        check(counts["chunk_prefill"] == 1 and counts["decode"] == 1
              and not counts["verify"] and (counts["cow_copy"] or 0) <= 1,
              f"{tag}: more than one compilation per program: {counts}")
        return out

    def both_paths(dtype):
        """The same requests on the fused decode block (the default) and on
        the per-op body's Pallas paged kernel -> {uid: index of the first
        token the two streams differ at}. The interpreter stands in for
        Mosaic on the CPU: "on" forces the fused block there, and the
        per-op body takes the reference."""
        cfg = GPTConfig(**({"max_seq": 256} if rehearsal else {}),
                        **sizes.model, dtype=dtype)
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        fused = run(cfg, params, "on" if rehearsal else "auto", "fused")
        per_op = run(cfg, params, "off",
                     "reference" if rehearsal else "pallas")
        diverged = {u: next(i for i, (a, b) in enumerate(
            zip(fused[u], per_op[u])) if a != b)
            for u in fused if fused[u] != per_op[u]}
        print(f"  {jnp.dtype(dtype).name}: {len(fused) - len(diverged)} of "
              f"{len(fused)} streams equal between the two decode paths"
              + (f"; first differing token per request: {diverged}"
                 if diverged else ""), flush=True)
        return diverged, len(fused)

    with Phase("serve"):
        # The deployed dtype. Greedy streams of two CORRECT bf16 paths can
        # part at a near-tie (logits are bf16: the top two of 50304 sit
        # within one ulp about one step in eight, and the fused block keeps
        # fp32 where the per-op body rounds to bf16), and once parted they
        # stay parted — so here equality is a coarse screen: a wrong kernel
        # parts every stream at its first decode steps.
        diverged, n = both_paths(jnp.bfloat16)
        check(len(diverged) <= n // 2,
              f"serve bf16: {len(diverged)} of {n} streams differ between "
              f"the fused and per-op paths: {diverged}")
        # The CPU parity test's oracle, on the compiled kernels: an fp32
        # model under matmul precision "highest" (XLA's dots and the
        # kernels' alike — the default would run both as one bf16 pass),
        # where the two paths agree to ~1e-6 and the streams must be EQUAL.
        # fp32 weights also stream through the fused block as (6, 1, 8)
        # tiles where bf16's are (1, 1, 4).
        with jax.default_matmul_precision("highest"):
            diverged, _ = both_paths(jnp.float32)
        check(not diverged, f"serve fp32: fused and per-op streams differ, "
                            f"first differing token per request: {diverged}")
        print(f"  device memory: {_memory_line()}", flush=True)


def four_chip_phase(sizes: Sizes, rehearsal: bool) -> None:
    import __graft_entry__

    with Phase("four chips"):
        print(f"  JAX reports {len(jax.devices())} devices: running the "
              f"four-chip legs in this process", flush=True)
        _train_leg(sizes, rehearsal, dp=4, tp=1, steps=5)
        _train_leg(sizes, rehearsal, dp=2, tp=2, steps=5)
        __graft_entry__.dryrun_multichip(4)
        print(f"  device memory: {_memory_line()}", flush=True)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="toy sizes on the CPU through the Pallas "
                         "interpreter: rehearses this script, proves "
                         "nothing about the chip")
    args = ap.parse_args(argv)
    rehearsal = args.cpu_rehearsal
    if rehearsal:
        print("CPU REHEARSAL, asked for with --cpu-rehearsal: toy sizes, "
              "interpreted kernels, four virtual devices. Not a chip run; "
              "no chip result is printed.", flush=True)
        from apex_tpu.utils.platform import pin_cpu_platform

        pin_cpu_platform(virtual_devices=4)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}; jax {jax.__version__}, jaxlib "
          f"{_version('jaxlib')}, libtpu {_version('libtpu')}, python "
          f"{sys.version.split()[0]}", flush=True)
    if not rehearsal and jax.default_backend() != "tpu":
        print(f"chip_smoke.py needs a TPU; the default backend is "
              f"{jax.default_backend()!r}. (A CPU rehearsal has to be asked "
              f"for: --cpu-rehearsal.)", file=sys.stderr, flush=True)
        return 2

    from apex_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_listener(_on_jax_event)
    print(f"compile cache: {cache_dir} "
          f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
          f"entries at start)", flush=True)

    sizes = REHEARSAL if rehearsal else CHIP
    t0 = time.perf_counter()
    kernel_phase(rehearsal)
    train_phase(sizes, rehearsal)
    serve_phase(sizes, rehearsal)
    if len(jax.devices()) >= 4:
        four_chip_phase(sizes, rehearsal)
    else:
        print(f"[four chips] not run: JAX reports {len(jax.devices())} "
              f"device(s)", flush=True)
    print(f"total {time.perf_counter() - t0:.1f}s; compile cache "
          f"{_CACHE['hits']} hits, {_CACHE['misses']} misses", flush=True)

    if _FAILURES:
        print(f"FAILED {len(_FAILURES)} check(s):", file=sys.stderr)
        for f in _FAILURES:
            print(f"  - {f}", file=sys.stderr)
        return 1
    result = {"ok": True, "device": device}
    if rehearsal:
        result["cpu_rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
