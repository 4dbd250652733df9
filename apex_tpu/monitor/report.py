"""Per-step MFU / bandwidth report — one join over three sources of truth.

The three observability fragments this unifies (each already exists, each
previously joined ad hoc by every consumer):

* ``apex_tpu.pyprof`` — MEASURED per-instruction time from the profiler
  trace (``measured_op_table``), the only source that answers "which op
  eats the step";
* ``apex_tpu.comm.accounting`` — bytes-on-wire priced from the compiled
  HLO's collectives (the EQuARX lesson: compression claims are validated
  on-wire, not in Python);
* analytic / XLA-cost-model FLOPs — the MFU denominator,
  cross-checked against ``compiled.cost_analysis()`` so it is never
  self-graded (:func:`mfu_check`).

:func:`step_report` runs a jittable step under the profiler and returns one
flat dict (step time, MFU, wire bytes + modeled ICI bandwidth, per-phase
time via :func:`phase_breakdown` over ``monitor.trace.split_scope``, trace
coverage) ready for :func:`apex_tpu.monitor.sink.json_record`.
:func:`hlo_stats` / :func:`mfu_check` are the compile-only (no-trace)
subset for hosts that cannot run the profiler.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax

from apex_tpu.analyze.hlo import as_text
from apex_tpu.comm.accounting import collective_report
from apex_tpu.monitor.trace import split_scope


def gpt_analytic_flops_per_token(n_params: int, num_layers: int,
                                 hidden: int, seq: int) -> float:
    """Standard decoder MFU accounting: ``6·N`` per token (fwd+bwd matmuls)
    plus causal attention ``6·L·hidden·seq``. Remat recompute is NOT
    credited. :func:`mfu_check` holds it against XLA's own count."""
    return float(6 * n_params + 6 * num_layers * hidden * seq)


def pipeline_bubble_fraction(num_microbatches: int, pp: int) -> float:
    """Idle fraction of the 1F1B ring schedule: ``(pp-1)/(M+pp-1)`` of the
    ticks are fill/drain (``pipeline_ring`` runs ``M + pp - 1`` ticks for
    ``M`` real microbatches). The per-tick cost itself is measured via the
    schedule's ``pp_stage``/``pp_ring_shift`` spans."""
    if num_microbatches <= 0 or pp <= 0:
        raise ValueError("num_microbatches and pp must be positive")
    return (pp - 1) / (num_microbatches + pp - 1)


def hlo_stats(compiled, default_group_size: Optional[int] = None
              ) -> Dict[str, Any]:
    """Compile-time stats of a ``jax.stages.Compiled``: XLA cost-model
    flops/bytes plus the ring-model wire bytes of every collective."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax returns [dict]
        ca = ca[0] if ca else {}
    ca = dict(ca or {})
    # one .as_text() through the shared analyze.hlo normalization (the
    # same entry point accounting parses through), priced once
    rep = collective_report(as_text(compiled), default_group_size)
    # NaN (not 0.0) when the backend's cost model omits a key: a reader
    # must see "unavailable", never "measured zero"
    return {
        "hlo_flops": float(ca.get("flops", float("nan"))),
        "hlo_bytes_accessed": float(ca.get("bytes accessed", float("nan"))),
        "wire_bytes": rep.wire_bytes,
        "collective_counts": {k: v for k, v in rep.counts.items() if v},
    }


def mfu_check(fn: Callable, *args: Any, analytic_flops: float,
              **kwargs: Any) -> Dict[str, Any]:
    """Compile-only MFU-denominator validation: compare the analytic flops
    model against ``cost_analysis()`` on the exact compiled step. Returns
    the stats dict plus
    ``analytic_flops`` and ``hlo_over_analytic``."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args, **kwargs).compile()
    out = hlo_stats(compiled)
    out["analytic_flops"] = float(analytic_flops)
    out["hlo_over_analytic"] = (
        round(out["hlo_flops"] / analytic_flops, 4) if analytic_flops
        else float("nan"))
    return out


def phase_breakdown(measured: Dict[str, Any]) -> Dict[str, float]:
    """ms/step per phase, from a ``measured_op_table`` result. The phase is
    :func:`apex_tpu.monitor.trace.split_scope`'s: ``fwd``, ``recompute``
    (the forward replayed under ``jax.checkpoint``) or ``bwd`` where
    differentiation wraps the operation, so the three passes of one scope
    stay apart; else the first user scope (``opt``, ``comm``, ``pp_stage``
    or any name). Unscoped ops land in ``<no-scope>``."""
    phases: Dict[str, float] = {}
    for r in measured["rows"]:
        # a row's scope is a path cut to ``depth`` components, with no
        # primitive at its end: give split_scope one to drop
        phase = split_scope(r["scope"] + "/_")[0] or "<no-scope>"
        phases[phase] = phases.get(phase, 0.0) + r["time_ms"]
    return dict(sorted(phases.items(), key=lambda kv: -kv[1]))


def step_report(
    fn: Callable,
    *args: Any,
    steps: int = 3,
    peak_flops: Optional[float] = None,
    analytic_flops_per_step: Optional[float] = None,
    depth: int = 2,
    default_group_size: Optional[int] = None,
    **kwargs: Any,
) -> Dict[str, Any]:
    """Measured per-step report of a jittable train step.

    Runs ``steps`` profiled executions (one compile, reused), joins the
    trace with the compiled HLO, and returns one flat JSON-ready dict::

        {backend, step_time_ms, flops_per_step, mfu, wire_bytes_per_step,
         wire_gbps, collective_counts, phase_ms, coverage_pct, rows}

    ``mfu`` uses ``analytic_flops_per_step`` when given (the honest
    accounting: remat recompute not credited), else the XLA cost-model
    flops. ``rows`` is the full per-op table
    (``pyprof.format_measured_table`` renders it) — pop it before sinking
    if you only want the summary line.
    """
    from apex_tpu.pyprof import measured_op_table

    if peak_flops is None:
        from apex_tpu.utils.platform import device_peaks

        peak_flops = device_peaks().bf16_flops_per_s
    measured = measured_op_table(
        fn, *args, steps=steps, depth=depth, peak_flops=peak_flops,
        **kwargs)
    stats = hlo_stats(measured["compiled"], default_group_size)

    # wall clock, NOT the attributed-row sum: a partial trace join would
    # understate the step by 1/coverage and inflate MFU/bandwidth
    step_ms = measured.get("wall_ms_per_step") or \
        measured["total_ms_per_step"]
    step_s = step_ms / 1e3
    flops = (analytic_flops_per_step if analytic_flops_per_step is not None
             else stats["hlo_flops"])
    out: Dict[str, Any] = {
        "backend": jax.default_backend(),
        "step_time_ms": round(step_ms, 3),
        "attributed_ms": round(measured["total_ms_per_step"], 3),
        "flops_per_step": flops,
        "wire_bytes_per_step": round(stats["wire_bytes"]),
        "wire_gbps": round(stats["wire_bytes"] / step_s / 1e9, 3)
        if step_s else 0.0,
        "collective_counts": stats["collective_counts"],
        "phase_ms": {k: round(v, 3)
                     for k, v in phase_breakdown(measured).items()},
        "coverage_pct": round(measured["coverage_pct"], 1),
        "rows": measured["rows"],
        "unattributed": measured["unattributed"],
    }
    out["mfu"] = round(flops / (step_s * peak_flops), 4) if step_s else 0.0
    if analytic_flops_per_step is not None and stats["hlo_flops"]:
        out["hlo_over_analytic"] = round(
            stats["hlo_flops"] / analytic_flops_per_step, 4)
    return out


def format_step_report(rep: Dict[str, Any]) -> str:
    """Two human lines: the headline and the phase split (the per-op table
    is ``pyprof.format_measured_table``'s job)."""
    head = (f"{rep['step_time_ms']:.3f} ms/step on {rep['backend']}"
            f" | {rep['flops_per_step'] / 1e9:.1f} GFLOP/step")
    if "mfu" in rep:
        head += f" | MFU {100.0 * rep['mfu']:.1f}%"
    head += (f" | wire {rep['wire_bytes_per_step'] / 1e6:.2f} MB/step"
             f" ({rep['wire_gbps']:.2f} GB/s)")
    phases = " ".join(f"{k}={v:.3f}ms" for k, v in rep["phase_ms"].items())
    return head + f"\nphases: {phases} | trace coverage " \
                  f"{rep['coverage_pct']:.1f}%"
