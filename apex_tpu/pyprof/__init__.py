"""Profiling toolkit (ref ``apex/pyprof``, ~5k LoC).

The reference has three parts: (1) ``nvtx.init()`` monkey-patches the torch
surface to emit NVTX ranges with call-site/shape/dtype payloads
(``nvtx/nvmarker.py``); (2) ``parse`` reads nvprof SQLite databases;
(3) ``prof`` maps kernels to layers and computes per-op FLOPs/bytes
(``prof/blas.py`` etc.).

TPU re-design: XLA already carries op provenance end-to-end, so the three
parts collapse to thin, robust wrappers:

* :func:`annotate` / :func:`annotate_function` — ``jax.named_scope`` ranges
  that show up in the XLA trace viewer (the nvtx.init capability, no
  monkey-patching needed: scopes attach to traced ops).
* :func:`trace` — ``jax.profiler.trace`` context writing a TensorBoard-
  loadable profile (the nvprof capture).
* :func:`cost_analysis` — compiled-HLO FLOPs/bytes per executable (the
  ``prof`` FLOP counting, exact instead of per-op formulas).
* :func:`report` / :func:`op_table` — per-op/per-layer attribution from the
  compiled HLO: every fused instruction with its ``named_scope`` layer path,
  FLOPs, bytes, and roofline time estimate (the ``parse``+``prof`` report).
* :func:`instruction_scopes` — which scope path (``op_name``) each compiled
  instruction carries, whether it only moves data, and its operands: what
  ``monitor.trace.scope_table`` hands a reader of a device trace.
* :func:`measured_report` / :func:`measured_op_table` — the MEASURED
  analogue: runs the step under ``jax.profiler``, parses the trace, and
  joins per-instruction measured time with the HLO flops/bytes (the
  reference's parse→prof kernel-time join, ``parse/kernel.py`` +
  ``prof/output.py``).
"""

from apex_tpu.pyprof.profiler import (  # noqa: F401
    annotate,
    annotate_function,
    cost_analysis,
    summary,
    trace,
)
from apex_tpu.pyprof.prof import (  # noqa: F401
    format_table,
    instruction_scopes,
    op_table,
    report,
)
from apex_tpu.pyprof.parse import (  # noqa: F401
    format_measured_table,
    load_trace_events,
    measured_op_table,
    measured_report,
)

__all__ = ["annotate", "annotate_function", "trace", "cost_analysis",
           "summary", "op_table", "format_table", "report",
           "measured_op_table", "format_measured_table", "measured_report",
           "load_trace_events", "instruction_scopes"]
