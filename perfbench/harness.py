"""What every kind of cell shares: the run's context, the look for a chip,
the compile cache and its counters, and the profiler window."""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Mapping, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    cell: str
    config: Mapping[str, Any]       # the configuration's file
    mix: Mapping[str, Any]          # the traffic mix's file
    chips: int
    seed: int
    seconds: float
    trace: bool
    t_process_start: float          # perf_counter at the top of run.py
    require_tpu: bool = True        # tests alone switch the look for a chip off
    counters: "JaxCounters" = None  # type: ignore[assignment]
    peaks: Any = None
    devices: List[Any] = dataclasses.field(default_factory=list)

    def info(self, **fields) -> None:
        """One line of facts on standard output, before the result's line."""
        print(json.dumps({"info": self.cell, **fields}), flush=True)


class JaxCounters:
    """Persistent-cache hits and misses, and compilations, from JAX's own
    monitoring events. ``mark()`` opens the measured window."""

    def __init__(self):
        self.cache_hits = 0
        self.cache_misses = 0
        self.compiles = 0
        self._at_mark: Optional[int] = None
        self.in_window = 0

    def install(self) -> None:
        import jax

        def on_event(event: str, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        def on_duration(event: str, _secs: float, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def mark(self) -> None:
        self._at_mark = self.compiles

    def close(self) -> None:
        if self._at_mark is not None:
            self.in_window = self.compiles - self._at_mark
            self._at_mark = None


def setup_jax(ctx: Context) -> None:
    """Fix the compile cache's directory (``JAX_COMPILATION_CACHE_DIR`` where
    it is set, else ``<checkout>/.jax_cache``: a fixed path inside the
    checkout), install the counters, and look for the chips."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if ctx.require_tpu:     # a test leaves the process's cache settings alone
        if not cache_dir:
            cache_dir = os.path.join(ROOT, ".jax_cache")
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        # every program, however small: after a cell's first run in a
        # checkout nothing compiles again
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    ctx.counters = JaxCounters()
    ctx.counters.install()

    devices = jax.devices()
    platform = devices[0].platform
    if ctx.require_tpu:
        if platform != "tpu":
            raise NoChip(f"{ctx.cell}: the default backend is {platform!r}; "
                         f"this benchmark measures the chip and does not "
                         f"fall back")
        if len(devices) < ctx.chips:
            raise NoChip(f"{ctx.cell}: needs {ctx.chips} chips, JAX reports "
                         f"{len(devices)}")
        from peaks import peaks_for

        ctx.peaks = peaks_for(devices[0].device_kind)
    ctx.devices = devices[:ctx.chips]
    ctx.info(platform=platform, device_kind=devices[0].device_kind,
             device_count=len(devices), chips_used=ctx.chips,
             compile_cache_dir=cache_dir, jax=jax.__version__)


def memory_by_device(ctx: Context) -> List[int]:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in ctx.devices]


def memory_peak_bytes(ctx: Context) -> int:
    """The peak on the fullest chip."""
    return max(memory_by_device(ctx))


class TraceWindow:
    """A profiler trace of part of the measured window, written under
    ``<checkout>/.perfbench_trace/<cell>`` (a fixed path, emptied first) and
    reduced after the window has closed."""

    def __init__(self, ctx: Context):
        self.dir = os.path.join(ROOT, ".perfbench_trace", ctx.cell)
        self.active = False
        self.done = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans come from TraceAnnotation
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.active = True

    def stop(self) -> None:
        import jax

        if self.active:
            jax.profiler.stop_trace()
            self.active = False
            self.done = True

    def reduce(self):
        """The loaded trace, or None where none was taken."""
        if not self.done:
            return None
        import xplane

        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            return None
        trace = xplane.load(paths[0])
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace


def span(name: str):
    """A host span of the harness's own on the profiler's clock."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation; ``nan``
    where there is nothing to take it of."""
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else float("nan")


def compared(name: str, value: float, limit: float) -> Dict[str, Any]:
    """One number of the comparison beside its limit (``value <= limit``)."""
    ok = bool(value == value and value <= limit)
    return {"name": name, "value": value, "limit": limit, "ok": ok}
