"""Named-span tracing — phases visible in the trace viewer AND the HLO.

Reference: ``apex.pyprof.nvtx`` ranges / ad-hoc ``torch.cuda.nvtx`` in hot
paths — host-side markers a profiler joins with kernel launches.

TPU design: one :func:`span`, which plants what fits where it runs:

* inside jitted code, at trace time: ``jax.named_scope`` — attaches the
  name to every op traced inside, so it rides the compiled HLO's op
  metadata (``op_name``). It costs nothing at run time and does not enter
  the compile-cache key. This is the marker that survives jit. (A
  ``TraceAnnotation`` there times the tracing.)
* on the host: ``jax.profiler.TraceAnnotation`` — a range on the
  profiler's clock, for eager and dispatch work (a disabled annotation
  with no profiler session open) — and a record in the host log
  (:func:`host_log`), which is always kept.

No flag, environment variable or config field switches any of them.

**The operator's contract: the names.** Readers (``perfbench/scopes.py``
and its metrics, ``PERF.md`` §3 and §5) match these letter for letter.

Device scopes of the train step (``transformer/testing/standalone_gpt.py``,
``bench.train_step_fn``), as :func:`split_scope` returns them::

    embed                       token + position embedding (+ dropout)
    layer                       the scanned layer body, and inside it
    layer/ln1  layer/ln2        the two LayerNorms
    layer/attn/qkv              QKV projection and the split into heads
    layer/attn/core             the flash call and any layout change for it
    layer/attn/out              merge of heads and output projection
    layer/mlp/fc  layer/mlp/act  layer/mlp/proj
    layer/residual              the two residual adds (and hidden dropout)
    final_ln                    the LayerNorm after the stack
    lm_head_loss                LM head + cross entropy, fused or not
    opt                         ``FusedAdam(...).step``: one fusion a leaf
                                that reads g, m, v, p and writes m, v, p
                                (no kernel below it since PR 31)
    scan_carry                  the scan's own slice of the stacked
                                parameters and write of the stacked
                                gradients, once a layer (no user scope)

Device scopes of the hybrid decoder's train step (``transformer/hybrid.py``:
gated-delta-rule linear-attention layers among full ones; the same
``bench.train_step_fn``, so ``opt`` and ``scan_carry`` as above)::

    embed                       token embedding (rows of the vocabulary held)
    layer                       one layer of a period, and inside it
    layer/linattn/proj          q, k, v, gate, beta and decay projections
    layer/linattn/conv          short causal convolutions, SiLU, q and k to
                                unit length
    layer/linattn/core          the gated delta rule, whatever implements it
                                (``ops/delta_rule.py``: the decay's sum and
                                what XLA runs round the kernels here, the
                                kernels below it as ``.../delta_rule_fwd``,
                                ``..._bwd``)
    layer/linattn/gate_norm     RMSNorm of a head's output times SiLU(gate)
    layer/linattn/out           output projection
    layer/attn/qkv  layer/attn/qk_norm  layer/attn/core  layer/attn/out
                                the full layer: projections, QK-norm, the
                                flash call and its layout changes, out
    layer/mlp/gate_up  layer/mlp/act  layer/mlp/down      the gated FFN
    layer/post_norm             the RMSNorm on a sublayer's output
    layer/residual              the two residual adds
    final_norm                  the RMSNorm after the stack
    lm_head_loss                untied head + cross entropy, fused

Device scopes of the block-diffusion decoder's train step
(``transformer/sdar.py``: grouped-query rotary attention under the
block-diffusion mask, routed experts; the same step, so ``opt`` and
``scan_carry`` as above)::

    noise                       the input pipeline's draws applied: the mask
                                id where a token is masked, the two copies
                                side by side, a weight 1/t a masked position
    embed                       token embedding (rows of the vocabulary held)
    layer                       one layer, and inside it
    layer/pre_norm              the RMSNorm before each sublayer
    layer/attn/qkv              q (all heads), k and v (the fewer K/V heads)
    layer/attn/qk_norm          RMSNorm over each head's width
    layer/attn/rope             the rotation of q and k
    layer/attn/core             the flash call and its layout changes; the
                                kernels below it as ``.../flash_fwd``,
                                ``.../flash_bwd_dq``, ``.../flash_bwd_dkv``
    layer/attn/out              merge of heads and output projection
    layer/moe/route             router product, softmax, top-k, weights
    layer/moe/dispatch          the pairs sorted by held expert and laid out
                                on tiles; their positions' rows gathered
                                (backward: the rows' cotangents summed onto
                                the positions, a gathered row a pair held)
    layer/moe/experts           the held experts' grouped products and the
                                gate between them; the kernels below it as
                                ``.../grouped_fwd``, ``.../grouped_dx``,
                                ``.../grouped_dw`` (where no kernel runs,
                                XLA's ``ragged-dot-*`` carries no scope: a
                                reader adds it here by name)
    layer/moe/combine           the results weighted and added back, a
                                gathered row a pair held
    layer/residual              the two residual adds
    final_norm                  the RMSNorm after the stack (noised half)
    lm_head_loss                untied head + weighted cross entropy, fused

Device scopes of the latent-attention decoder's train step
(``transformer/deepseek.py``: DeepSeek-V2's block, a leading dense layer
written out before the scanned expert layers; the same step, so ``opt`` and
``scan_carry`` as above; ``layer/moe/*`` as in the block-diffusion decoder)::

    embed                       token embedding (rows of the vocabulary held)
    layer                       one layer (dense or expert), and inside it
    layer/pre_norm              the RMSNorm before each sublayer
    layer/attn/q_proj           the query projection and its split into heads
    layer/attn/kv_down          the latent's down projection ``x W_kva``
                                (512 + the 64 of the one rotated key)
    layer/attn/kv_norm          RMSNorm of the latent (``kv_a_layernorm``)
    layer/attn/kv_up            the latent expanded into each head's key
                                (unrotated part) and value
    layer/attn/rope             the rotation of the queries' rotated part and
                                of the one rotated key (YaRN, interleaved
                                pairs), its broadcast into every head's key
    layer/attn/core             the flash call (keys of 192 over values of
                                128) and its layout changes; the kernels
                                below it as in the other families
    layer/attn/out              merge of heads and output projection
    layer/mlp/gate_up  layer/mlp/act  layer/mlp/down
                                the dense layer's gated FFN
    layer/shared/gate_up  layer/shared/act  layer/shared/down
                                an expert layer's shared expert (not under
                                ``layer/moe``: a reader of the routed layer
                                files an unknown part of it under dispatch)
    layer/moe/route  layer/moe/dispatch  layer/moe/experts  layer/moe/combine
                                the routed experts, as above
    layer/aux_loss              the balance loss a sequence, from the
                                router's scores and choices
    layer/residual              the residual adds
    final_norm                  the RMSNorm after the stack
    lm_head_loss                untied head + cross entropy, fused

**Counters of a routed layer** (``ROUTING_COUNTERS``; a step's, from the
held experts' loads that the step itself returns, ``train_step_fn``'s fourth
result, through ``transformer.moe.routing_facts``): ``pairs_held``
((position, expert) pairs that landed on an expert held here),
``pairs_uniform`` (what a uniform router would send), ``max_load_over_mean``
(among the held experts), ``tiled_rows`` (the rows the experts' spans take in
the grouped product's buffer), ``passes_run`` (the passes over that buffer),
``padding_rows`` (rows of those passes that hold no pair), and the batch's
``masked_positions``. The step's fourth result also carries each layer's
``held_places`` (how many positions hold exactly 0 .. top_k pairs with a row
in the first pass), and ``routing_facts`` given it adds two counters:
``rows_gathered`` (the rows one sum of the buffer's rows back onto the
positions gathers, the restore of the positions' order included: the
combine's forward, and as many again the dispatch's backward) and
``rows_gathered_over_held`` (that over the pairs with a row in the pass;
``positions x top_k`` over them is what a gather a place would read). A kind
that prints these two and the step's ``aux_loss`` (the latent-attention
decoder's balance loss, summed over its expert layers: the step's fourth
result carries each layer's) lists them in a second tuple,
``ROUTING_COUNTERS_MORE``: the latent-attention cell's kind does
(``perfbench/kinds/train_dsv2.py``); the block-diffusion cell's prints
``ROUTING_COUNTERS`` alone, and its test holds its line to that tuple as it
is.

each under one phase: ``fwd``, ``recompute`` (the forward replayed under
``jax.checkpoint``), ``bwd``, or the first user scope where no
differentiation wraps the operation (``opt``, ``comm``, ...).

Device scopes of the serving programs (``serve/decode.py``): ``embed``,
``layer`` with ``ln1``, ``attn/qkv``, ``kv_write``, ``kv_read`` (the paged
attention call), ``attn/out``, ``ln2``, ``mlp/fc``, ``mlp/act``,
``mlp/proj``, ``residual``; then ``final_ln`` and ``lm_head``.

Host spans (``TraceAnnotation`` and a record; the narrowest one that
covers an idle gap names it): the train step's ``train_step``, one a call of
the step with the call's index from 1 as ``call`` (``apex_tpu.train``), and
``scope_table`` (:func:`scope_table`'s own compile); the engine's
``prefill`` (one chunk's dispatch, and the fence on a prompt's last chunk),
``prefill.admit`` (``_try_admit``), ``decode`` with ``decode.dispatch`` (the
call of the decode or verify program) and ``decode.fence``
(``np.asarray(toks)``) inside it, and ``decode.retire`` (per-slot
bookkeeping to the end of ``step()``); ``comm``, ``fwd_bwd``, ``pp_stage`` /
``pp_ring_shift``, ``transfer`` and ``scrape`` elsewhere in the package.

**The host log** (:func:`host_log`: a copy, as :class:`HostRecord` s in the
order they closed). Its records are host spans (``kind`` ``"span"``) and
JAX's compile path, ``kind`` ``"trace"`` (``jaxpr_trace_duration``),
``"lower"`` (``jaxpr_to_mlir_module_duration``) and ``"compile"``
(``backend_compile_duration``: compiled, or ``cached`` where a persistent
cache hit fell inside), one a pass of a program (``program``: JAX's
``fun_name`` less ``jit(...)``, so ``train_step``); a pass nested in another
of its kind (the primitives a step's trace traces) is folded into the
outermost one's ``count``. Each carries the innermost host span open around
it (``parent``) and the call index of the innermost ``train_step`` open
around it (``call``): a compilation names the step call it held up. All
times are ``time.time()``'s, the clock JAX stamps these events with. The
listener is installed when this module is imported (``apex_tpu.train``
imports it when it builds the step); what runs before that is not in the
log. Bounded: the first ``HOST_LOG_HEAD`` records (16,384: a job's set-up)
are kept whole, then a ring of the newest ``HOST_LOG_RING`` (16,384), so a
job of any length holds at most 32,768.

**Which scope a compiled instruction belongs to.** A program registers a
thunk under the name its module has in a device trace
(:func:`register_program`); a reader asks :func:`scope_table` for
``{instruction name: {"op_name", "opcode", "moves_only"}}`` of the program
compiled at given shapes. Nothing is lowered or compiled until a reader
asks.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import re
import threading
import time
from typing import Callable, Dict, Iterator, List, NamedTuple, Optional, Tuple

import jax
from jax._src import core as _core
from jax._src import monitoring as _monitoring

# what a routed layer counts a step (the contract above)
ROUTING_COUNTERS = ("pairs_held", "pairs_uniform", "max_load_over_mean",
                    "tiled_rows", "passes_run", "padding_rows",
                    "masked_positions")
# and what a kind that reads ``held_places`` and the balance loss adds
ROUTING_COUNTERS_MORE = ("rows_gathered", "rows_gathered_over_held",
                         "aux_loss")


@contextlib.contextmanager
def span(name: str, *, call: Optional[int] = None) -> Iterator[None]:
    """Named range. Inside jitted code, at trace time: ``named_scope`` (→ HLO
    op metadata → the scope table; nesting composes into ``outer/inner``)
    and a ``TraceAnnotation``. On the host: a ``TraceAnnotation`` (the
    trace viewer's host row, with ``call`` as its argument where given) and
    a record in the host log (:func:`host_log`)."""
    if not _core.trace_state_clean():
        with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
            yield
        return
    note = {} if call is None else {"call": call}
    with jax.profiler.TraceAnnotation(name, **note):
        start = _LOG.opened(name, call)
        try:
            yield
        finally:
            _LOG.closed(name, call, start)


def span_function(fn: Callable = None, *, name: Optional[str] = None):
    """Decorator form of :func:`span` (ref ``nvtx/nvmarker.py`` function
    wrapping): the function body traces under ``name`` (default: its
    qualname)."""
    if fn is None:
        return functools.partial(span_function, name=name)

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with span(name or fn.__qualname__):
            return fn(*args, **kwargs)

    return wrapped


# -- from an instruction's op_name to (phase, scope) -------------------------

_JIT = re.compile(r"\b(?:jit|pjit|xla_call)\([^()]*\)")
_WRAPPER = re.compile(
    r"\b(?:jvp|transpose|vmap|pmap|remat|checkpoint|custom_jvp|custom_vjp)\(")
# path components JAX writes itself: control flow, calls and the transforms'
# own sub-computations. What is left of a path is the user's.
_PLUMBING = frozenset((
    "while", "body", "cond", "body_fun", "cond_fun", "closed_call",
    "core_call", "checkpoint", "rematted_computation", "remat", "remat2",
    "shard_map", "pjit", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_jvp_call", "custom_lin", "branch_0_fun", "branch_1_fun"))


def split_scope(op_name: str) -> Tuple[str, str]:
    """``(phase, scope)`` of a compiled instruction's ``op_name``.

    ``phase`` is ``recompute`` if the path holds ``rematted_computation``,
    else ``bwd`` if it holds ``transpose(``, else ``fwd`` if it holds
    ``jvp(``, else the first user scope (``opt``, ``comm``, ...; ``""``
    where there is none). ``scope`` is the user's part of the path
    (``layer/attn/qkv``): without ``jit(...)``, the wrappers of
    differentiation, JAX's own components (``while/body``,
    ``closed_call``, ``checkpoint``, ...) and the primitive at the end. An
    operation directly under ``while/body`` with no user scope is the
    loop's own: the scan slicing its stacked inputs
    (``dynamic_slice``, ``squeeze``), writing its stacked outputs
    (``dynamic_update_slice``) and counting: ``scan_carry``.
    """
    path = op_name.split(";", 1)[0]     # a fused instruction may list several
    flat = _WRAPPER.sub("", _JIT.sub("", path)).replace(")", "")
    parts = [p for p in flat.split("/") if p][:-1]    # less the primitive
    user = []
    for p in parts:
        # the transposed body repeats the scope the scan's equation carried
        if p not in _PLUMBING and (not user or user[-1] != p):
            user.append(p)
    scope = "/".join(user)
    if not scope and parts[-2:] == ["while", "body"]:
        scope = "scan_carry"
    if "rematted_computation" in path:
        phase = "recompute"
    elif "transpose(" in path:
        phase = "bwd"
    elif "jvp(" in path:
        phase = "fwd"
    else:
        phase = user[0] if user else ""
    return phase, scope


# -- which scope each compiled instruction belongs to -------------------------

_PROGRAMS: Dict[str, Callable] = {}


def register_program(name: str, lower: Callable) -> None:
    """Register ``lower(**shape) -> jax.stages.Lowered`` under the name the
    program's module has in a device trace (``jit_train_step``). The thunk
    closes over the jitted function and what its shapes and shardings are
    made from, and over no array; nothing is lowered here. A later
    registration under the same name replaces the earlier one."""
    _PROGRAMS[name] = lower


def scope_table(name: str, **shape) -> Optional[Dict[str, Dict]]:
    """``{instruction name: {"op_name", "opcode", "moves_only"}}`` of the
    program registered as ``name``, compiled at ``shape`` (what its thunk
    takes: ``rows`` and ``seq`` for the train step); ``None`` where no such
    program is registered.

    The persistent compile cache's key leaves metadata out, so a cache hit
    may hand back an executable compiled from a tree with other scope names
    (or none), and its text then shows *that* tree's metadata. This one
    compile therefore runs with the metadata in the key. The instruction
    names do not depend on metadata: a reader still proves, by name and
    opcode, that the table is of the executable it traced.
    """
    from apex_tpu.pyprof.prof import instruction_scopes

    lower = _PROGRAMS.get(name)
    if lower is None:
        return None
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        with span("scope_table"):
            compiled = lower(**shape).compile()
    finally:
        jax.config.update(flag, before)
    return instruction_scopes(compiled.as_text())


# -- the host log ------------------------------------------------------------------

HOST_LOG_HEAD = 16384       # the first records, kept whole: a job's set-up
HOST_LOG_RING = 16384       # then the newest ones

# JAX's compile path: one event a pass, timed with ``time.time()``
_COMPILE_PATH = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_WRAPPED = re.compile(r"^(?:jit|pjit|pmap|xla_pmap)\((.*)\)$")


class HostRecord(NamedTuple):
    name: str                   # the span's name, or JAX's ``fun_name``
    kind: str                   # "span", "trace", "lower" or "compile"
    start: float                # seconds on ``time.time()``'s clock
    end: float
    parent: Optional[str]       # the innermost host span open around it
    program: Optional[str]      # compile path: ``fun_name`` less ``jit(...)``
    call: Optional[int]         # the dispatch's call index, its own or the
                                # innermost one open around it
    count: int = 1              # passes of this kind folded in: itself and
                                # those nested inside it
    cached: Optional[bool] = None   # compile: read from the persistent cache


class _Thread(threading.local):
    def __init__(self):
        self.open: List[Tuple[str, Optional[int]]] = []    # host spans, innermost last
        self.depth = dict.fromkeys(_COMPILE_PATH.values(), 0)
        self.folded = dict.fromkeys(_COMPILE_PATH.values(), 0)
        self.hit = False


class HostLog:
    """The records of one process: the first ``head`` whole, then a ring of
    the newest ``ring``. A pass of the compile path nested in another of its
    kind (the primitives a step's trace traces) is folded into the
    outermost one's ``count``."""

    def __init__(self, head: int = HOST_LOG_HEAD, ring: int = HOST_LOG_RING):
        self._head_size = head
        self._head: List[HostRecord] = []
        self._ring = collections.deque(maxlen=ring)
        self._lock = threading.Lock()
        self._thread = _Thread()

    def records(self) -> List[HostRecord]:
        with self._lock:
            return self._head + list(self._ring)

    def _add(self, rec: HostRecord) -> None:
        with self._lock:
            if len(self._head) < self._head_size:
                self._head.append(rec)
            else:
                self._ring.append(rec)

    def _around(self) -> Tuple[Optional[str], Optional[int]]:
        """(innermost open span, innermost call index open) on this thread."""
        spans = self._thread.open
        call = next((c for _, c in reversed(spans) if c is not None), None)
        return (spans[-1][0] if spans else None), call

    def opened(self, name: str, call: Optional[int]) -> float:
        self._thread.open.append((name, call))
        return time.time()

    def closed(self, name: str, call: Optional[int], start: float) -> None:
        end = time.time()
        self._thread.open.pop()
        parent, outer = self._around()
        self._add(HostRecord(name, "span", start, end, parent, None,
                             outer if call is None else call))

    def entered(self, kind: str) -> None:
        t = self._thread
        t.depth[kind] += 1
        if kind == "compile" and t.depth[kind] == 1:
            t.hit = False

    def left(self, kind: str, fun_name: str, start: float, end: float) -> None:
        t = self._thread
        t.depth[kind] = max(t.depth[kind] - 1, 0)
        if t.depth[kind]:
            t.folded[kind] += 1
            return
        count, t.folded[kind] = 1 + t.folded[kind], 0
        m = _WRAPPED.match(fun_name)
        parent, call = self._around()
        self._add(HostRecord(fun_name, kind, start, end, parent,
                             m.group(1) if m else fun_name, call, count,
                             t.hit if kind == "compile" else None))

    def cache_hit(self) -> None:
        self._thread.hit = True


_LOG = HostLog()


def host_log() -> List[HostRecord]:
    """A copy of this process's host log, in the order records closed."""
    return _LOG.records()


def _on_start(event: str, _value, **_kw) -> None:
    kind = _COMPILE_PATH.get(event)
    if kind:
        _LOG.entered(kind)


def _on_time_span(event: str, start: float, end: float, fun_name: str = "",
                  **_kw) -> None:
    kind = _COMPILE_PATH.get(event)
    if kind:
        _LOG.left(kind, fun_name, start, end)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _LOG.cache_hit()


def _install() -> None:
    """Subscribe the log to JAX's compile path; a second call adds nothing.
    A pass announces its start as a scalar (its ``time.time()``) and its end
    as a time span."""
    for listeners, register, fn in (
            (_monitoring.get_scalar_listeners, _monitoring.register_scalar_listener,
             _on_start),
            (_monitoring.get_event_time_span_listeners,
             _monitoring.register_event_time_span_listener, _on_time_span),
            (_monitoring.get_event_listeners, _monitoring.register_event_listener,
             _on_event)):
        if fn not in listeners():
            register(fn)


_install()
