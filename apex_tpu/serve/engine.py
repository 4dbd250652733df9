"""Iteration-level continuous-batching inference engine.

The TPU-v3-pod MLPerf lesson (arXiv 1909.09756) applied to serving:
throughput at scale is slot occupancy — a static batch drains to its
longest member while every other chip's slot idles. This engine batches at
**iteration granularity** (Orca/vLLM's scheduling, rebuilt for jitted JAX
programs): a fixed grid of decode slots advances one token per step, and
between steps finished requests retire and new ones are admitted into the
freed slots. Nothing retraces, and three stacked throughput optimizations
ride the same paged cache:

* **chunked prefill, bounded compilation** — prompts are processed as
  fixed-size chunks (``ServeConfig.prefill_chunk``) interleaved into the
  decode loop: ONE compiled chunk program + ONE decode program (+ at most
  one verify program per speculative k) for the engine's whole lifetime —
  the PR-5 prompt bucket ladder and its ``n_buckets`` compile set are
  gone, and with them the TTFT-vs-throughput tradeoff of picking a ladder
  (``compile_counts()`` is the gate ``tests/test_serve.py`` pins). The
  MPK argument (arXiv 2512.22219) in scheduler form: decode is
  latency-bound, so the whole step — embed, every layer, paged attention,
  sampling — is one compiled program, one dispatch. With
  ``ServeConfig.megakernel`` the argument goes one level deeper: each
  layer's interior (LN + QKV + paged attend + MLP, int8 dequant in
  kernel) becomes ONE fused Pallas block (``serve.megakernel``), cutting
  the per-layer op count ~14 -> 2 inside that single program.
* **prefix caching** — the block allocator is content-addressed
  (``kv_cache.BlockAllocator(prefix_cache=True)``): admission looks up
  the longest cached prefix of the prompt at block granularity and only
  prefills the tail, so a shared system prompt costs ZERO prefill flops
  after its first admission; retired requests' cached blocks park in an
  evictable LRU at refcount 0 and are reclaimed only under memory
  pressure. Copy-on-write covers the one divergent-write case (a
  fully-cached prompt recomputing its final position) — a shared block is
  never mutated.
* **self-speculative decoding** — an optional host-side drafter
  (``serve.drafter``, prompt-lookup n-gram by default, pluggable for a
  small model) proposes up to k tokens per slot; ONE q_len=k+1
  paged-attention call (``gpt_verify_step``) verifies all of them,
  amortizing the dispatch-bound decode step k-fold. The engine accepts
  the longest run matching its own position-keyed draws, so streams are
  BITWISE identical to non-speculative decode (greedy and sampled);
  rejected drafts need no rollback — their K/V writes are masked by every
  later context window and overwritten when real tokens arrive.

* **donation-safe state** — the paged KV pools (``serve.kv_cache``) are
  donated through every chunk/decode/verify call; slot bookkeeping
  (block tables, lengths, last tokens, keys) stays host-side numpy with
  CACHED device mirrors — an array is re-uploaded only after an
  admission/retirement/decode actually changed it
  (``engine.transfer_counts`` pins it).
* **request-order invariance** — greedy streams are bitwise equal to
  single-request decode of each prompt, and sampled streams equal under
  the same key, because per-slot computation is row-independent and
  sampling keys are request-intrinsic (``serve.sampling``).

Weights arrive through ``resilience.CheckpointManager.latest_valid()``
(:meth:`InferenceEngine.from_checkpoint`) — a serving replica points at
the training job's checkpoint directory and refuses torn/corrupt saves.
Telemetry rides the ``monitor`` pipeline: an in-graph ``Metrics`` pytree
out of the decode/verify programs plus host-side step records (tokens/s,
TTFT, occupancy, modeled decode flops/MFU, KV bytes, chunked-prefill
backlog, speculative proposed/accepted, cumulative prefix-cache hit
counters) into a ``JsonlSink``; ``python -m apex_tpu.monitor.view``
summarizes all of them.

Monitor **tier 2** (request-level attribution, constant memory): every
request runs a lifecycle timeline — ``submitted → admitted →
prefill_start/end → first_token → decode_chunk* → retired`` on one
monotonic clock through an optional ``monitor.EventLog`` (JSONL + Chrome
trace via ``monitor.chrome_trace``, one Perfetto track per slot and per
request) — and retirement FOLDS the request's latencies (TTFT, mean
per-output-token, queue wait, end-to-end) into streaming
``monitor.Histogram``\\ s plus an optional ``monitor.SloTracker``, then
drops every per-uid entry. Engine state stays O(slots + backlog) across
millions of requests when ``retain_streams=False`` (per-request token
streams go to the ``on_retire`` callback instead of an ever-growing
dict); :meth:`InferenceEngine.stats` returns the histograms, latency
quantiles, prefix-cache/speculation counters and goodput-under-SLO
report as one JSON-serializable dict.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from apex_tpu.monitor.events import EventLog
from apex_tpu.monitor.hist import DEFAULT_LATENCY_SPEC, HistSpec, Histogram
from apex_tpu.monitor.meter import Meter, modeled_request_flops
from apex_tpu.monitor.metrics import Metrics
from apex_tpu.monitor.slo import SloSpec, SloTracker
from apex_tpu.monitor.trace import register_program, span
from apex_tpu.serve.adapters import (
    AdapterRegistry,
    adapter_pool_bytes,
    init_adapter_pool,
    write_adapter,
)
from apex_tpu.serve.decode import (
    ensure_dense_ffn,
    gpt_decode_step,
    gpt_prefill_chunk,
    gpt_verify_step,
)
from apex_tpu.serve.drafter import Drafter, NGramDrafter
from apex_tpu.serve.kv_cache import (
    BlockAllocator,
    KVCacheConfig,
    copy_block,
    init_kv_cache,
    kv_cache_bytes,
    kv_read_bytes,
    kv_write_bytes_per_token,
    prefix_block_hashes,
)
from apex_tpu.serve.sampling import SamplingConfig, request_key, sample

Pytree = Any


def default_bucket_ladder(max_context: int, start: int = 16
                          ) -> Tuple[int, ...]:
    """COMPAT SHIM (pre-chunked-prefill API): powers-of-two prompt buckets
    up to ``max_context``. The engine no longer compiles per-bucket
    prefill programs — prompts stream through one fixed-size chunk program
    — but the ladder remains for callers that sized workloads by it."""
    out = []
    b = start
    while b < max_context:
        out.append(b)
        b *= 2
    out.append(max_context)
    return tuple(out)


@dataclasses.dataclass
class Request:
    """One generation request. ``seed`` feeds the request's sampling key
    (default: crc32 of the uid — stable across runs and admission orders);
    irrelevant under greedy decoding. ``tenant`` names the paying party
    for the cluster router's weighted fair queueing (the single engine
    ignores it). ``adapter`` names the tenant's LoRA adapter (None =
    the base model): admission binds it to a resident pool slot and an
    unknown name is SHED via ``on_reject``, never served on the wrong
    weights."""

    uid: str
    tokens: Sequence[int]
    max_new_tokens: int = 64
    seed: Optional[int] = None
    tenant: str = "default"
    adapter: Optional[str] = None

    def sampling_seed(self) -> int:
        if self.seed is not None:
            return int(self.seed)
        return zlib.crc32(self.uid.encode())


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine shape knobs (all static — they pick the compiled programs)."""

    num_slots: int = 4
    block_size: int = 16
    # total pool blocks; default = num_slots * blocks-per-max-context (no
    # oversubscription). Smaller pools admit fewer concurrent requests —
    # admission simply waits for frees, it never preempts.
    num_blocks: Optional[int] = None
    # COMPAT SHIM: the pre-chunked-prefill bucket ladder. Accepted and
    # surfaced via engine.buckets/bucket_for for old callers, but NO
    # prefill program is compiled per bucket anymore.
    prefill_buckets: Optional[Tuple[int, ...]] = None
    # tokens per prefill chunk: ONE compiled prefill program, interleaved
    # into the decode loop one chunk per step
    prefill_chunk: int = 32
    # content-addressed block reuse across requests (zero prefill flops
    # for cached shared prefixes)
    prefix_cache: bool = True
    # self-speculative decoding: draft up to spec_k tokens per slot per
    # step and verify them in one q_len=spec_k+1 call; 0 disables
    spec_k: int = 0
    spec_ngram: int = 3  # n-gram order of the default prompt-lookup drafter
    # fused per-layer decode megakernel (serve.megakernel): "auto" uses it
    # when supported AND a compiled Mosaic backend is available, "on"
    # forces it (interpret mode off-TPU — the parity tests' mode; raises
    # when the model shape is unsupported), "off" keeps the per-op
    # gpt_decode_step program
    megakernel: str = "auto"
    max_context: Optional[int] = None  # default: model cfg.max_seq
    eos_id: Optional[int] = None
    # "none" | "int8" | "int4" (comm.quantize codec; int4 = nibble-packed
    # codes + bf16 group scales, half the int8 pool bytes — doubles the
    # contexts a fixed KV budget serves)
    kv_quant: str = "none"
    # int4 scale-group length along head_dim (None: one scale per vector)
    kv_group: Optional[int] = None
    # per-tenant paged LoRA (serve.adapters): rank of the A/B factors
    # (0 disables — the programs are built WITHOUT adapter arguments and
    # trace identically to the pre-adapter engine) and the number of
    # concurrently-resident adapters (pool slots beyond the reserved
    # base slot 0)
    lora_rank: int = 0
    max_adapters: int = 0
    # model-parallel serving (apex_tpu.serve.sharded): a ParallelismPlan
    # whose ONE sharding term (tp= / pp= / data='fsdp') picks the
    # residency strategy — ``sharded.build_engine`` reads it; None keeps
    # the single-chip engine. Validated inference-legal at validate()
    # time via plan.serve_overrides() (optimizer-coupled knobs refused).
    plan: Optional[Any] = None
    sampling: SamplingConfig = dataclasses.field(
        default_factory=SamplingConfig)

    def validate(self) -> None:
        if self.num_slots <= 0:
            raise ValueError("num_slots must be positive")
        if self.block_size <= 0:
            raise ValueError("block_size must be positive")
        if self.num_blocks is not None and self.num_blocks <= 0:
            raise ValueError("num_blocks must be positive when given")
        if self.prefill_chunk <= 0:
            raise ValueError("prefill_chunk must be positive")
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        if self.spec_ngram < 1:
            raise ValueError("spec_ngram must be >= 1")
        if self.megakernel not in ("auto", "on", "off"):
            raise ValueError(f"megakernel must be 'auto', 'on' or 'off', "
                             f"got {self.megakernel!r}")
        if self.max_context is not None and self.max_context <= 0:
            raise ValueError("max_context must be positive when given")
        if self.kv_quant not in ("none", "int8", "int4"):
            raise ValueError(f"kv_quant must be 'none', 'int8' or 'int4', "
                             f"got {self.kv_quant!r}")
        if self.kv_group is not None and self.kv_quant != "int4":
            raise ValueError("kv_group only applies to kv_quant='int4'")
        if self.lora_rank < 0:
            raise ValueError("lora_rank must be >= 0")
        if self.max_adapters < 0:
            raise ValueError("max_adapters must be >= 0")
        if self.lora_rank > 0 and self.max_adapters < 1:
            raise ValueError("lora_rank > 0 needs max_adapters >= 1")
        if self.max_adapters > 0 and self.lora_rank == 0:
            raise ValueError("max_adapters > 0 needs lora_rank > 0")
        if self.plan is not None:
            if not hasattr(self.plan, "serve_overrides"):
                raise ValueError(
                    f"plan must be a ParallelismPlan "
                    f"(apex_tpu.parallel.plan), got {type(self.plan)!r}")
            # runs the inference-legality validation eagerly: a plan that
            # only makes sense feeding an optimizer dies here, not
            # mid-build inside serve.sharded
            self.plan.serve_overrides()
            if self.lora_rank > 0:
                raise NotImplementedError(
                    "paged LoRA adapters are single-device for now — the "
                    "AdapterPool is not plan-sharded (lora_rank needs "
                    "plan=None)")
        self.sampling.validate()


# the engine's latency dimensions; each gets a streaming Histogram
_HIST_NAMES = ("ttft_ms", "tpot_ms", "queue_ms", "e2e_ms",
               "decode_step_ms", "verify_step_ms")

# host arrays with cached device mirrors (uploaded only when dirty)
_MIRROR_NAMES = ("block_tables", "seq_lens", "last_tokens", "active",
                 "keys", "adapter_ids")


@dataclasses.dataclass
class _SlotState:
    request: Request
    blocks: List[int]          # every block the slot holds a ref on
    generated: List[int]
    # prompt + generated, maintained incrementally so the drafter reads
    # it without an O(prompt_len) re-concatenation every step
    history: List[int]
    prompt_len: int
    prefill_pos: int           # prompt tokens cached so far (chunk cursor)
    cached_tokens: int         # prompt tokens served by the prefix cache
    # (block_id, hash, end_pos): commit to the content map once the chunk
    # cursor passes end_pos (the block is then fully written)
    pending_commits: List[Tuple[int, int, int]]
    # request timeline, ms on the engine's one monotonic clock
    t_submit_ms: float
    t_first_ms: float = 0.0
    queue_ms: float = 0.0
    ttft_ms: float = 0.0
    chunk_start_ms: float = 0.0  # start of the decode chunk being accumulated
    chunk_done: int = 0          # tokens already covered by emitted chunks
    adapter_id: int = 0          # resident pool slot this request decodes on


class InferenceEngine:
    """Continuous-batching engine over one parameter pytree.

    Tensor parallelism: pass ``tp_axis``/``tp_size`` AND a ``transform``
    that shard_maps the chunk/decode/verify python callables over that
    axis (params TP-sharded by ``gpt_param_specs``-style specs, everything
    else replicated) — the programs then route through the
    ``tensor_parallel`` layers with vocab-gathered logits, and the KV
    pools hold the ``num_heads / tp_size`` LOCAL heads. The default
    (``tp_axis=None``, identity transform) drives the single-device
    programs — the stock-jax path the acceptance tests pin.

    ``sink``: an ``apex_tpu.monitor.JsonlSink`` (or None) receiving one
    record per engine step. ``peak_flops_per_s``: chip peak for the
    modeled decode-MFU column (omitted -> mfu not reported).

    ``drafter``: a ``serve.drafter.Drafter`` for the speculative path
    (default when ``spec_k > 0``: ``NGramDrafter(spec_ngram)``). The
    drafter only proposes — acceptance is decided by the engine's own
    verify pass, so a bad drafter can never change a stream.

    Tier-2 telemetry: ``events`` (a ``monitor.EventLog``) records every
    request's lifecycle; ``slo`` (a ``monitor.SloSpec``) turns on
    goodput/violation accounting; ``hist_spec`` overrides the latency
    bucket ladder; ``chunk_tokens`` sets the decode-chunk EVENT span
    granularity (unrelated to ``prefill_chunk``, the compiled chunk
    size). ``retain_streams=False`` keeps per-request state O(slots):
    retirement hands the stream to ``on_retire(uid, tokens)`` (or drops
    it) instead of growing the ``finished`` dict forever.
    """

    def __init__(
        self,
        params: Pytree,
        cfg,  # transformer.testing.GPTConfig
        serve_cfg: Optional[ServeConfig] = None,
        *,
        base_key=None,
        sink=None,
        peak_flops_per_s: Optional[float] = None,
        transform: Optional[Callable[[Callable], Callable]] = None,
        tp_axis: Optional[str] = None,
        tp_size: int = 1,
        use_pallas: Optional[bool] = None,
        events: Optional[EventLog] = None,
        slo: Optional[SloSpec] = None,
        hist_spec: Optional[HistSpec] = None,
        retain_streams: bool = True,
        on_retire: Optional[Callable[[str, List[int]], None]] = None,
        chunk_tokens: int = 16,
        drafter: Optional[Drafter] = None,
        gather_layer: Optional[Callable] = None,
        on_reject: Optional[Callable[[Request, Dict[str, Any]],
                                     None]] = None,
        meter: Optional[Meter] = None,
        meter_worker: str = "engine",
    ):
        scfg = serve_cfg or ServeConfig()
        scfg.validate()
        ensure_dense_ffn(cfg.num_experts)
        if (tp_axis is None) != (tp_size == 1):
            raise ValueError("pass tp_axis together with tp_size > 1 "
                             "(and a shard_map transform)")
        if cfg.num_heads % tp_size:
            raise ValueError(f"num_heads ({cfg.num_heads}) not divisible "
                             f"by tp_size ({tp_size})")
        self.params = params
        self.cfg = cfg
        self.serve_cfg = scfg
        if scfg.max_context is not None and scfg.max_context > cfg.max_seq:
            raise ValueError(
                f"max_context ({scfg.max_context}) exceeds the model's "
                f"max_seq ({cfg.max_seq})")
        self.max_context = scfg.max_context or cfg.max_seq
        bs = scfg.block_size
        self._blocks_per_slot = -(-self.max_context // bs)
        num_blocks = (scfg.num_blocks if scfg.num_blocks is not None
                      else scfg.num_slots * self._blocks_per_slot)
        self._tp_axis = tp_axis
        self.kv_cfg = KVCacheConfig(
            num_layers=cfg.num_layers, num_heads=cfg.num_heads // tp_size,
            head_dim=cfg.head_dim, num_blocks=num_blocks, block_size=bs,
            dtype=cfg.dtype, quantized=scfg.kv_quant != "none",
            bits=4 if scfg.kv_quant == "int4" else 8,
            group_size=scfg.kv_group)
        self.allocator = BlockAllocator(num_blocks,
                                        prefix_cache=scfg.prefix_cache)
        self.cache = init_kv_cache(self.kv_cfg)
        self.drafter: Optional[Drafter] = None
        if scfg.spec_k > 0:
            self.drafter = (drafter if drafter is not None
                            else NGramDrafter(ngram=scfg.spec_ngram))
        elif drafter is not None:
            raise ValueError("drafter given but spec_k == 0 — set "
                             "ServeConfig.spec_k to enable speculation")
        # per-tenant paged LoRA: the donated AdapterPool + the host-side
        # registry (None/None when disabled — the programs are then built
        # WITHOUT adapter arguments, trace-identical to the pre-adapter
        # engine)
        self._lora_pool = None
        self.adapters: Optional[AdapterRegistry] = None
        self._adapter_load_ms_total = 0.0
        if scfg.lora_rank > 0:
            if tp_axis is not None:
                raise NotImplementedError(
                    "paged LoRA adapters are single-device for now — the "
                    "AdapterPool is not TP-sharded (lora_rank needs "
                    "tp_axis=None)")
            self._lora_pool = init_adapter_pool(cfg, scfg.lora_rank,
                                                scfg.max_adapters)
            self.adapters = AdapterRegistry(scfg.max_adapters)
        n = scfg.num_slots
        self._block_tables = np.zeros((n, self._blocks_per_slot), np.int32)
        self._seq_lens = np.zeros((n,), np.int32)
        self._last_tokens = np.zeros((n,), np.int32)
        self._active = np.zeros((n,), bool)
        self._keys = np.zeros((n, 2), np.uint32)
        self._adapter_ids = np.zeros((n,), np.int32)
        # device mirrors of the host arrays above: uploaded lazily, reused
        # until a host mutation marks them dirty (the satellite gate —
        # steady-state decode re-uploads ONLY what changed)
        self._dev_cache: Dict[str, Any] = {}
        self.transfer_counts: Dict[str, int] = {
            nm: 0 for nm in _MIRROR_NAMES}
        self._slots: List[Optional[_SlotState]] = [None] * n
        # admission-ordered slots with prompt tokens still to prefill; the
        # front slot gets one chunk per step (FCFS-to-completion: best
        # TTFT under interleaving)
        self._prefill_queue: collections.deque = collections.deque()
        self._pending: collections.deque = collections.deque()
        self._finished: Dict[str, List[int]] = {}
        self._base_key = (base_key if base_key is not None
                          else jax.random.PRNGKey(0))
        self._sink = sink
        self._peak = peak_flops_per_s
        self._step_idx = 0
        self._tokens_generated = 0
        self._t_start: Optional[float] = None
        # tier-2 telemetry: one monotonic clock (the EventLog's when
        # given, so event timestamps and latency folds agree), streaming
        # histograms, optional SLO accounting — all O(1) per request
        self._events = events
        self._t_anchor = time.perf_counter()
        if chunk_tokens < 1:
            raise ValueError(
                f"chunk_tokens must be >= 1, got {chunk_tokens}")
        self._chunk_tokens = int(chunk_tokens)
        hspec = hist_spec or DEFAULT_LATENCY_SPEC
        self.hists: Dict[str, Histogram] = {
            name: Histogram(hspec) for name in _HIST_NAMES}
        # tier-4 attribution: the engine-LOCAL decomposition from the slot
        # timeline (queue/prefill/decode; transfer and stall only exist at
        # the cluster, whose event-tap AttributionAccumulator owns them)
        self._attrib_hists: Dict[str, Histogram] = {
            c: Histogram(hspec) for c in ("queue", "prefill", "decode")}
        self._attrib_n = 0
        # tier-4 metering: retirement charges the request's tenant into
        # the (possibly cluster-shared) ledger — exactly once, by
        # whichever engine retires it
        self._meter = meter
        self._meter_worker = meter_worker
        # the tracker SHARES the engine's histograms (decode_step_ms is
        # engine-only): one fold per retirement, one source of truth for
        # both the stats() quantiles and the slo_report
        self._slo = (SloTracker(slo, hists={
            d: self.hists[d]
            for d in ("ttft_ms", "tpot_ms", "queue_ms", "e2e_ms")})
            if slo is not None else None)
        self._retain_streams = retain_streams
        self._on_retire = on_retire
        # overload behavior: with an on_reject hook, a request the pool
        # can NEVER fit is handed back as a structured rejection (the
        # cluster router's shed path) instead of run()'s deadlock-loud
        # RuntimeError; default behavior (raise) unchanged
        self._on_reject = on_reject
        self._rejected = 0
        self._completed = 0
        # throughput-optimization counters (stats() + step records)
        self._prefix_blocks_hit = 0
        self._prefix_blocks_needed = 0
        self._prefill_tokens_saved = 0
        self._prefill_flops_saved = 0.0
        self._cow_copies = 0
        self._chunks_run = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._verify_steps = 0
        self._decode_steps = 0
        self._n_params = sum(
            x.size for x in jax.tree_util.tree_leaves(params))
        # model-parallel serving telemetry hook (serve.sharded sets it):
        # a zero-arg callable returning the flat plan fields stats()
        # merges — plan, hbm_model_bytes, weight_gather_ms,
        # pp_bubble_fraction. None on single-chip engines (the fields
        # are then absent, and monitor.regress skips what isn't there).
        self.plan_stats: Optional[Callable[[], Dict[str, Any]]] = None
        wrap = transform if transform is not None else (lambda f: f)
        # FSDP weight residency (serve.sharded): per-layer param
        # materializer threaded into the paged forwards — params then
        # carry resident shards, gathered for one layer body at a time
        self._gather_layer = gather_layer
        self._use_pallas = use_pallas
        self._megakernel = self._resolve_megakernel()
        self._build_programs(wrap)
        self._register_programs()

    def _register_programs(self) -> None:
        """Hand ``monitor.trace.scope_table`` the decode and chunk-prefill
        programs under the module names a device trace gives them
        (``jit_decode``, ``jit_chunk_prefill``), as thunks over shapes:
        nothing is lowered here, and the thunks hold no array (the
        engine's shapes are fixed, so they take no argument). An engine
        whose programs are not single jitted functions registers none."""
        def shape_of(a):
            return jax.ShapeDtypeStruct(
                a.shape, jax.dtypes.canonicalize_dtype(a.dtype),
                sharding=getattr(a, "sharding", None))

        state = jax.tree.map(shape_of, (self.params, self.cache) + (
            () if self._lora_pool is None else (self._lora_pool,)))
        mirrors = {nm: shape_of(getattr(self, "_" + nm))
                   for nm in _MIRROR_NAMES}
        lora = self._lora_pool is not None
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        chunk = jax.ShapeDtypeStruct((self.serve_cfg.prefill_chunk,),
                                     jnp.int32)

        def row(nm):
            return jax.ShapeDtypeStruct(mirrors[nm].shape[1:],
                                        mirrors[nm].dtype)

        decode_args = state + tuple(mirrors[nm] for nm in (
            "last_tokens", "seq_lens", "active", "block_tables", "keys")) + (
            (mirrors["adapter_ids"],) if lora else ())
        prefill_args = state + (chunk, i32, i32, row("block_tables"),
                                row("keys")) + ((i32,) if lora else ())
        for fn, args in ((self._decode, decode_args),
                         (self._chunk_prefill, prefill_args)):
            if hasattr(fn, "lower"):    # a staged engine's are host loops
                register_program("jit_" + fn.__name__,
                                 functools.partial(fn.lower, *args))

    def _resolve_megakernel(self) -> bool:
        """ServeConfig.megakernel -> whether the decode AND verify
        programs are the fused per-layer block. ``auto`` requires a
        compiled Mosaic backend (the interpreter saves no dispatch);
        ``on`` forces it and raises on unsupported shapes (TP, LoRA,
        MoE, layers whose live TILE set exceeds the VMEM budget) with
        the measured refusal reason. An ``auto`` fallback on a COMPILED
        backend warns once per reason — a 10x slower serve run must be
        diagnosable from the log, not only from the bench line's
        ``decode_kernel`` field."""
        from apex_tpu.ops._pallas_util import compiled_backend
        from apex_tpu.serve.megakernel import (megakernel_refusal,
                                               warn_megakernel_fallback)

        mode = self.serve_cfg.megakernel
        if mode == "off":
            return False
        # the verify step feeds spec_k+1 rows per slot; gate on the
        # larger live set so speculation never flips the kernel choice
        q = self.serve_cfg.spec_k + 1
        if self._tp_axis is not None:
            reason = "TP-sharded programs ride the per-op layer body"
        elif self._gather_layer is not None:
            reason = ("plan-sharded (FSDP weight-resident) params ride "
                      "the per-op layer body")
        elif self.serve_cfg.lora_rank > 0:
            reason = ("per-slot LoRA adapters (lora_rank > 0) ride the "
                      "per-op layer body")
        else:
            reason = megakernel_refusal(self.cfg, self.kv_cfg,
                                        allow_interpret=(mode == "on"),
                                        q=q)
        if mode == "on":
            if reason is not None:
                raise ValueError(
                    f"megakernel='on' but the fused decode block does "
                    f"not support this configuration: {reason} — use "
                    f"megakernel='off'/'auto'")
            return True
        if reason is not None:
            if compiled_backend():
                warn_megakernel_fallback(reason)
            return False
        return True

    @property
    def megakernel_enabled(self) -> bool:
        """Whether decode steps run the fused per-layer Pallas block."""
        return self._megakernel

    @property
    def decode_kernel(self) -> str:
        """The decode path this engine actually runs: ``fused`` (the
        per-layer megakernel), ``pallas`` (gather-attend kernel inside
        the per-op layer body) or ``reference`` (pure-JAX gather +
        softmax). Emitted in :meth:`stats` and the bench record so the
        stage-12 regression gate can tell a kernel FALLBACK from a real
        regression."""
        if self._megakernel:
            return "fused"
        from apex_tpu.ops._pallas_util import compiled_backend
        from apex_tpu.serve.decode import paged_kernel_refusal

        use_pallas = self._use_pallas
        if use_pallas is None:
            use_pallas = compiled_backend() and paged_kernel_refusal(
                self.kv_cfg, compiled=True) is None
        return "pallas" if use_pallas else "reference"

    @property
    def verify_kernel(self) -> Optional[str]:
        """The speculative verify path this engine actually runs:
        ``None`` when ``spec_k == 0`` (no verify program exists), else
        ``fused``/``pallas``/``reference`` — the same resolution as
        :attr:`decode_kernel`, because one ``megakernel`` flag drives
        both jit sites. Emitted in :meth:`stats` so the verify A/B gate
        can tell a kernel fallback from a regression."""
        if self.serve_cfg.spec_k <= 0:
            return None
        return self.decode_kernel

    # -- device mirrors ---------------------------------------------------
    def _dirty(self, *names: str) -> None:
        for nm in names:
            self._dev_cache.pop(nm, None)

    def _dev(self, name: str):
        """Cached device copy of host array ``self._<name>`` — uploads
        only when a mutation marked it dirty (``transfer_counts`` tallies
        actual uploads; the identity test pins reuse)."""
        arr = self._dev_cache.get(name)
        if arr is None:
            arr = jnp.asarray(getattr(self, "_" + name))
            self._dev_cache[name] = arr
            self.transfer_counts[name] += 1
        return arr

    # -- program construction (the ONLY jit sites) -------------------------
    def _build_programs(self, wrap) -> None:
        cfg, kv_cfg, scfg = self.cfg, self.kv_cfg, self.serve_cfg

        tp_axis = self._tp_axis
        if self._lora_pool is not None:
            # the adapter-enabled closures take the donated pool as a
            # second donated argument and return it untouched
            self._build_lora_programs(wrap)
            return

        def chunk_prefill(params, cache, tokens, start, n_valid, block_row,
                          key):
            cache, logits = gpt_prefill_chunk(
                params, tokens, start, n_valid, cache, block_row, cfg,
                kv_cfg, tp_axis=tp_axis, use_pallas=self._use_pallas,
                gather_layer=self._gather_layer)
            # the draw for the token that will sit at position start+n_valid
            # — meaningful only on a prompt's FINAL chunk; junk otherwise
            tok = sample(logits[None], key[None],
                         jnp.reshape(start + n_valid, (1,)), scfg.sampling)
            return cache, tok[0]

        use_mega = self._megakernel

        def decode(params, cache, last_tokens, seq_lens, active,
                   block_tables, keys):
            if use_mega:
                from apex_tpu.serve.megakernel import gpt_decode_step_fused

                cache, logits = gpt_decode_step_fused(
                    params, last_tokens, seq_lens, active, cache,
                    block_tables, cfg, kv_cfg)
            else:
                cache, logits = gpt_decode_step(
                    params, last_tokens, seq_lens, active, cache,
                    block_tables, cfg, kv_cfg, tp_axis=tp_axis,
                    use_pallas=self._use_pallas,
                    gather_layer=self._gather_layer)
            toks = sample(logits, keys, seq_lens + 1, scfg.sampling)
            # in-graph step metrics: donation-safe, fixed treedef — the
            # monitor.Metrics contract (zero extra compilations)
            m = Metrics().record(
                active_slots=jnp.sum(active),
                context_tokens=jnp.sum(
                    jnp.where(active, seq_lens + 1, 0)))
            return cache, toks, m

        def verify(params, cache, fed_tokens, seq_lens, n_fed, active,
                   block_tables, keys):
            if use_mega:
                from apex_tpu.serve.megakernel import gpt_verify_step_fused

                cache, logits = gpt_verify_step_fused(
                    params, fed_tokens, seq_lens, n_fed, active, cache,
                    block_tables, cfg, kv_cfg)
            else:
                cache, logits = gpt_verify_step(
                    params, fed_tokens, seq_lens, n_fed, active, cache,
                    block_tables, cfg, kv_cfg, tp_axis=tp_axis,
                    use_pallas=self._use_pallas,
                    gather_layer=self._gather_layer)
            k1 = fed_tokens.shape[1]
            draw_pos = seq_lens[:, None] + 1 + jnp.arange(k1)[None, :]
            toks = sample(logits, keys, draw_pos, scfg.sampling)
            m = Metrics().record(
                active_slots=jnp.sum(active),
                context_tokens=jnp.sum(
                    jnp.where(active, seq_lens + 1, 0)))
            return cache, toks, m

        def cow(cache, src, dst):
            # local closure (not the module-level copy_block directly):
            # jax keys jit caches on function identity, and compile_counts
            # must report THIS engine's compiles only
            return copy_block(cache, src, dst)

        self._chunk_prefill = jax.jit(wrap(chunk_prefill),
                                      donate_argnums=(1,))
        self._decode = jax.jit(wrap(decode), donate_argnums=(1,))
        self._verify = (jax.jit(wrap(verify), donate_argnums=(1,))
                        if scfg.spec_k > 0 else None)
        # copy-on-write block copy (src/dst traced -> one compile, ever)
        self._cow = jax.jit(wrap(cow), donate_argnums=(0,))

    def _build_lora_programs(self, wrap) -> None:
        """The adapter-enabled program set: same jit sites, same keys,
        ONE compile each — the AdapterPool rides every call as a SECOND
        donated argument (argnum 2, next to the KV cache at 1) and is
        returned untouched (identity output aliasing: no copy, no leak —
        ``analyze.adapters`` pins it). Which adapters are resident or
        active is pure DATA (pool contents + the ``adapter_ids`` mirror),
        so loads/unloads/swaps never retrace."""
        cfg, kv_cfg, scfg = self.cfg, self.kv_cfg, self.serve_cfg

        tp_axis = self._tp_axis

        def chunk_prefill(params, cache, lora, tokens, start, n_valid,
                          block_row, key, aid):
            cache, logits = gpt_prefill_chunk(
                params, tokens, start, n_valid, cache, block_row, cfg,
                kv_cfg, tp_axis=tp_axis, use_pallas=self._use_pallas,
                adapters=lora, adapter_id=aid)
            tok = sample(logits[None], key[None],
                         jnp.reshape(start + n_valid, (1,)), scfg.sampling)
            return cache, lora, tok[0]

        def decode(params, cache, lora, last_tokens, seq_lens, active,
                   block_tables, keys, adapter_ids):
            cache, logits = gpt_decode_step(
                params, last_tokens, seq_lens, active, cache,
                block_tables, cfg, kv_cfg, tp_axis=tp_axis,
                use_pallas=self._use_pallas, adapters=lora,
                adapter_ids=adapter_ids)
            toks = sample(logits, keys, seq_lens + 1, scfg.sampling)
            m = Metrics().record(
                active_slots=jnp.sum(active),
                context_tokens=jnp.sum(
                    jnp.where(active, seq_lens + 1, 0)))
            return cache, lora, toks, m

        def verify(params, cache, lora, fed_tokens, seq_lens, n_fed,
                   active, block_tables, keys, adapter_ids):
            cache, logits = gpt_verify_step(
                params, fed_tokens, seq_lens, n_fed, active, cache,
                block_tables, cfg, kv_cfg, tp_axis=tp_axis,
                use_pallas=self._use_pallas, adapters=lora,
                adapter_ids=adapter_ids)
            k1 = fed_tokens.shape[1]
            draw_pos = seq_lens[:, None] + 1 + jnp.arange(k1)[None, :]
            toks = sample(logits, keys, draw_pos, scfg.sampling)
            m = Metrics().record(
                active_slots=jnp.sum(active),
                context_tokens=jnp.sum(
                    jnp.where(active, seq_lens + 1, 0)))
            return cache, lora, toks, m

        def cow(cache, src, dst):
            return copy_block(cache, src, dst)

        self._chunk_prefill = jax.jit(wrap(chunk_prefill),
                                      donate_argnums=(1, 2))
        self._decode = jax.jit(wrap(decode), donate_argnums=(1, 2))
        self._verify = (jax.jit(wrap(verify), donate_argnums=(1, 2))
                        if scfg.spec_k > 0 else None)
        self._cow = jax.jit(wrap(cow), donate_argnums=(0,))

    def programs(self) -> Dict[str, Optional[Callable]]:
        """The engine's jitted programs, keyed like :meth:`compile_counts`
        — hand this straight to ``analyze.recompile_guard`` to pin a
        workload's compile behavior in place::

            with recompile_guard(engine.programs(), budget=0):
                engine.run(requests)   # steady state: no new compiles
        """
        return {"chunk_prefill": self._chunk_prefill,
                "decode": self._decode,
                "verify": self._verify,
                "cow_copy": self._cow}

    def compile_counts(self) -> Dict[str, Optional[int]]:
        """Jit-cache sizes of the engine programs — the compile-count gate
        reads this (expected: exactly 1 chunked prefill + 1 decode, plus
        <= 1 verify per distinct spec-k shape and <= 1 CoW copy). One
        implementation: ``analyze.recompile.compile_counts``."""
        from apex_tpu.analyze.recompile import compile_counts

        return compile_counts(self.programs())

    # -- submission --------------------------------------------------------
    @property
    def buckets(self) -> Tuple[int, ...]:
        """COMPAT SHIM: the ladder old callers sized workloads by. The
        engine compiles no per-bucket programs anymore."""
        return tuple(sorted(self.serve_cfg.prefill_buckets
                            or default_bucket_ladder(self.max_context)))

    def bucket_for(self, prompt_len: int) -> int:
        """COMPAT SHIM: smallest compat-ladder bucket holding the prompt
        (no compilation consequence since chunked prefill)."""
        for b in self.buckets:
            if b >= prompt_len:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest bucket "
            f"({self.buckets[-1]})")

    def submit(self, request: Request) -> None:
        p = len(request.tokens)
        if p < 1:
            raise ValueError(f"{request.uid}: empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError(f"{request.uid}: max_new_tokens must be >= 1")
        if p >= self.max_context:
            raise ValueError(
                f"{request.uid}: prompt ({p}) must leave room to generate "
                f"(max_context {self.max_context})")
        if request.adapter is not None and self.adapters is None:
            raise ValueError(
                f"{request.uid}: adapter {request.adapter!r} requested "
                f"but adapters are disabled (ServeConfig.lora_rank == 0)")
        t = self._now_ms()
        self._pending.append((request, t))
        if self._events is not None:
            self._events.emit("submitted", request.uid, t_ms=t,
                              prompt_tokens=p,
                              max_new_tokens=request.max_new_tokens)
            self._events.gauge("queue_depth", len(self._pending), t_ms=t)

    def _now_ms(self) -> float:
        """Ms on the engine's one monotonic clock (the EventLog's anchor
        when events are wired, so both artifacts share timestamps)."""
        if self._events is not None:
            return self._events.now_ms()
        return (time.perf_counter() - self._t_anchor) * 1e3

    # -- admission ---------------------------------------------------------
    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _total_tokens(self, request: Request) -> int:
        # cached tokens at retirement: prompt + all generated but the last
        # (never fed back); budget the full generation window, clamped
        return min(len(request.tokens) + request.max_new_tokens,
                   self.max_context)

    def _resolve_adapter(self, request: Request) -> Optional[int]:
        """Bind the head request to its adapter's pool slot (refcount
        acquired — released at retirement/eviction). None means the
        request was SHED (unknown adapter, reject hook wired): the head
        was popped, the admission loop continues. Without a hook the
        unknown adapter raises — the single-engine analogue of run()'s
        deadlock-loud pool_exhausted."""
        if request.adapter is None:
            return 0
        assert self.adapters is not None  # submit() refused otherwise
        aid = self.adapters.acquire(request.adapter)
        if aid is not None:
            return aid
        self._pending.popleft()
        self._rejected += 1
        info = {"reason": "unknown_adapter", "adapter": request.adapter,
                "resident": sorted(self.adapters.resident())}
        if self._on_reject is not None:
            self._on_reject(request, info)
            if self._events is not None:
                self._events.emit("shed", request.uid,
                                  reason="unknown_adapter",
                                  adapter=request.adapter)
            return None
        raise KeyError(
            f"{request.uid}: unknown adapter {request.adapter!r} "
            f"(resident: {info['resident']}) — load_adapter() it first "
            f"or wire on_reject to shed")

    def _try_admit(self) -> int:
        admitted = 0
        while self._pending:
            slot = self._free_slot()
            if slot is None:
                break
            request, t_submit = self._pending[0]
            aid = self._resolve_adapter(request)
            if aid is None:
                continue  # shed: head popped, try the next request
            n_blocks = self.kv_cfg.blocks_for_tokens(
                self._total_tokens(request))
            bs = self.kv_cfg.block_size
            hashes = (prefix_block_hashes(request.tokens, bs)
                      if self.serve_cfg.prefix_cache else [])
            # acquire the longest cached prefix FIRST (a ref pins those
            # blocks against the eviction alloc() may run next)
            hit = self.allocator.lookup(hashes)
            # FULL-prompt hit (p % bs == 0): the final prompt position
            # must be recomputed for its logits, and that write lands
            # inside the last matched block — the one genuinely divergent
            # write. Copy-on-write: one extra private block to copy the
            # shared content into; the sharers' block is never mutated
            # (bitwise-pinned by test).
            cow = bool(hit) and len(hit) * bs >= len(request.tokens)
            fresh = self.allocator.alloc(
                n_blocks - len(hit) + (1 if cow else 0))
            if fresh is None and cow:
                # pool too tight for the CoW copy: degrade to dropping the
                # last matched block and prefilling it into a fresh one
                self.allocator.free([hit[-1]])
                hit = hit[:-1]
                cow = False
                fresh = self.allocator.alloc(n_blocks - len(hit))
            if fresh is None:
                if hit:
                    self.allocator.free(hit)  # release the acquired refs
                if aid and request.adapter is not None:
                    # drop the adapter pin too — re-acquired on retry
                    self.adapters.release(request.adapter)
                break  # pool full: wait for a retirement to free blocks
            self._pending.popleft()
            self._admit(slot, request, hit, fresh, cow, hashes, t_submit,
                        aid)
            admitted += 1
        return admitted

    def _admit(self, slot: int, request: Request, hit: List[int],
               fresh: List[int], cow: bool, hashes: List[int],
               t_submit_ms: float, adapter_id: int = 0) -> None:
        p = len(request.tokens)
        bs = self.kv_cfg.block_size
        n_hit = len(hit)
        if cow:
            # fresh[0] is the private replacement for the last matched
            # block: copy the shared content on device, swap it into the
            # table, drop OUR ref on the shared source (sharers keep it)
            src, dst = hit[-1], fresh[0]
            self.cache = self._cow(self.cache, jnp.int32(src),
                                   jnp.int32(dst))
            self.allocator.free([src])
            blocks = hit[:-1] + [dst] + fresh[1:]
            self._cow_copies += 1
        else:
            blocks = hit + fresh
        hit_len = n_hit * bs
        cached = min(hit_len, p - 1)  # position p-1 always recomputed
        n_full = p // bs
        if self.serve_cfg.prefix_cache:
            self._prefix_blocks_needed += n_full
            self._prefix_blocks_hit += min(n_hit, n_full)
        self._prefill_tokens_saved += cached
        # modeled flops the cache saved: 2N matmul per skipped token plus
        # the causal attention term (the decode_flops_per_token model
        # summed over the skipped positions)
        self._prefill_flops_saved += (
            2.0 * self._n_params * cached
            + 4.0 * self.cfg.num_layers * self.cfg.hidden
            * (cached * (cached + 1)) / 2.0)
        t_adm = self._now_ms()
        queue_ms = t_adm - t_submit_ms
        if self._events is not None:
            self._events.emit("admitted", request.uid, t_ms=t_adm,
                              slot=slot, queue_ms=round(queue_ms, 3),
                              cached_tokens=cached)
            self._events.emit("prefill_start", request.uid, t_ms=t_adm,
                              slot=slot, prompt_tokens=p,
                              chunk=self.serve_cfg.prefill_chunk)
        row = np.zeros((self._blocks_per_slot,), np.int32)
        row[:len(blocks)] = blocks
        key = np.asarray(
            request_key(self._base_key, request.sampling_seed()), np.uint32)
        # blocks the tail prefill will fill: committed to the content map
        # as the chunk cursor passes their end (never before — a block is
        # addressable only once fully written); empty when the prefix
        # cache is off (no hashes computed)
        commits = [(int(row[j]), hashes[j], (j + 1) * bs)
                   for j in range(n_hit, n_full)] if hashes else []
        if cow:
            # the CoW copy is content-complete once position p-1 rewrites;
            # commit is a no-op while the shared source stays mapped but
            # re-registers the content if the source gets evicted first
            commits.append((int(blocks[n_hit - 1]), hashes[n_full - 1], p))
        state = _SlotState(request=request, blocks=blocks, generated=[],
                           history=[int(t) for t in request.tokens],
                           prompt_len=p, prefill_pos=cached,
                           cached_tokens=cached, pending_commits=commits,
                           t_submit_ms=t_submit_ms, queue_ms=queue_ms,
                           adapter_id=adapter_id)
        self._slots[slot] = state
        self._block_tables[slot] = row
        self._keys[slot] = key
        self._adapter_ids[slot] = adapter_id
        self._dirty("block_tables", "keys", "adapter_ids")
        self._prefill_queue.append(slot)

    # -- chunked prefill ---------------------------------------------------
    def _prefill_backlog_tokens(self) -> int:
        """Prompt tokens admitted but not yet chunk-prefilled (the
        chunked-prefill backlog depth gauge)."""
        return sum(s.prompt_len - s.prefill_pos
                   for s in self._slots
                   if s is not None and s.prefill_pos < s.prompt_len)

    def _run_prefill_chunk(self) -> bool:
        """One fixed-size chunk for the front of the prefill queue; on the
        prompt's final chunk, sample the first token and promote the slot
        to the decode grid."""
        if not self._prefill_queue:
            return False
        slot = self._prefill_queue[0]
        state = self._slots[slot]
        assert state is not None
        C = self.serve_cfg.prefill_chunk
        c = state.prefill_pos
        p = state.prompt_len
        n_valid = min(C, p - c)
        tokens = np.zeros((C,), np.int32)
        tokens[:n_valid] = np.asarray(
            state.request.tokens[c:c + n_valid], np.int32)
        with span("prefill"):
            if self._lora_pool is None:
                self.cache, tok = self._chunk_prefill(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.int32(c), jnp.int32(n_valid),
                    self._dev("block_tables")[slot], self._dev("keys")[slot])
            else:
                self.cache, self._lora_pool, tok = self._chunk_prefill(
                    self.params, self.cache, self._lora_pool,
                    jnp.asarray(tokens), jnp.int32(c), jnp.int32(n_valid),
                    self._dev("block_tables")[slot], self._dev("keys")[slot],
                    self._dev("adapter_ids")[slot])
            state.prefill_pos = c + n_valid
            self._chunks_run += 1
            done = state.prefill_pos >= p
            if done:
                first = int(tok)  # fence: TTFT includes the round-trip
        # full blocks the cursor passed are now content-addressable
        while (state.pending_commits
               and state.pending_commits[0][2] <= state.prefill_pos):
            b, h, _ = state.pending_commits.pop(0)
            self.allocator.commit(b, h)
        if not done:
            return True
        self._prefill_queue.popleft()
        t_first = self._now_ms()
        ttft_ms = t_first - state.t_submit_ms
        if self._events is not None:
            self._events.emit("prefill_end", state.request.uid,
                              t_ms=t_first, slot=slot)
            self._events.emit("first_token", state.request.uid,
                              t_ms=t_first, slot=slot,
                              ttft_ms=round(ttft_ms, 3))
        if self._t_start is None:
            self._t_start = time.perf_counter()
        self._tokens_generated += 1
        state.generated.append(first)
        state.history.append(first)
        state.t_first_ms = t_first
        state.ttft_ms = ttft_ms
        state.chunk_start_ms = t_first
        state.chunk_done = 1
        self._seq_lens[slot] = p
        self._last_tokens[slot] = first
        self._active[slot] = True
        self._dirty("seq_lens", "last_tokens", "active")
        if self._events is not None:
            self._events.gauge("occupancy", self.occupancy(), t_ms=t_first)
        if self._should_retire(state, first):
            self._retire(slot)
        return True

    # -- retirement --------------------------------------------------------
    def _should_retire(self, state: _SlotState, tok: int) -> bool:
        if (self.serve_cfg.eos_id is not None
                and tok == self.serve_cfg.eos_id):
            return True
        if len(state.generated) >= state.request.max_new_tokens:
            return True
        # feeding the next token would write at position p + generated - 1,
        # which must stay inside the context window: continue while
        # p + generated <= max_context, retire beyond
        return (state.prompt_len + len(state.generated)
                > self.max_context)

    def _retire(self, slot: int) -> None:
        """Retirement FOLDS the request's timeline into the streaming
        histograms (and SLO tracker) and drops every per-uid entry — the
        O(slots) state contract. Streams are retained only when the
        engine was built with ``retain_streams=True`` (the default, for
        ``run()``'s return value) or handed to ``on_retire``. Freed
        blocks that carry a content address PARK in the allocator's
        evictable LRU — the prefix cache outlives its requests."""
        state = self._slots[slot]
        assert state is not None
        uid = state.request.uid
        now = self._now_ms()
        n_gen = len(state.generated)
        e2e_ms = now - state.t_submit_ms
        tpot_ms = ((now - state.t_first_ms) / (n_gen - 1)
                   if n_gen > 1 else None)
        if self._slo is not None:
            # the tracker folds into the SAME shared histograms
            self._slo.observe(ttft_ms=state.ttft_ms, tpot_ms=tpot_ms,
                              queue_ms=state.queue_ms, e2e_ms=e2e_ms)
        else:
            self.hists["ttft_ms"].add([state.ttft_ms])
            self.hists["queue_ms"].add([state.queue_ms])
            self.hists["e2e_ms"].add([e2e_ms])
            if tpot_ms is not None:
                self.hists["tpot_ms"].add([tpot_ms])
        if self._events is not None:
            if n_gen > state.chunk_done:  # final partial decode chunk
                self._events.emit(
                    "decode_chunk", uid, t_ms=now, slot=slot,
                    start_ms=round(state.chunk_start_ms, 3),
                    n_tokens=n_gen - state.chunk_done)
            self._events.emit(
                "retired", uid, t_ms=now, slot=slot, n_tokens=n_gen,
                ttft_ms=round(state.ttft_ms, 3), e2e_ms=round(e2e_ms, 3),
                tpot_ms=(round(tpot_ms, 3) if tpot_ms is not None
                         else None))
        # tier-4: engine-local latency attribution — the three local
        # components partition e2e exactly (queue + prefill + decode,
        # with prefill = ttft - queue and decode = e2e - ttft)
        self._attrib_hists["queue"].add([max(0.0, state.queue_ms)])
        self._attrib_hists["prefill"].add(
            [max(0.0, state.ttft_ms - state.queue_ms)])
        self._attrib_hists["decode"].add([max(0.0, e2e_ms - state.ttft_ms)])
        self._attrib_n += 1
        if self._meter is not None:
            # charge-once-at-retirement: a migrated request's source
            # engine EVICTS (never retires), so the destination's single
            # charge covers the whole request — Σ tenants == fleet totals
            held_s = max(0.0, now - (state.t_submit_ms
                                     + state.queue_ms)) / 1e3
            usage = {
                "flops": modeled_request_flops(
                    self._n_params, self.cfg.num_layers, self.cfg.hidden,
                    state.prompt_len, n_gen, state.cached_tokens),
                "kv_block_s": len(state.blocks) * held_s,
            }
            if state.adapter_id and state.request.adapter is not None:
                usage["adapter_s"] = held_s
            self._meter.charge(state.request.tenant,
                               worker=self._meter_worker, t_ms=now,
                               tokens=n_gen, requests=1, **usage)
        self._completed += 1
        if self._retain_streams:
            self._finished[uid] = state.generated
        if self._on_retire is not None:
            self._on_retire(uid, state.generated)
        self.allocator.free(state.blocks)
        if state.adapter_id and state.request.adapter is not None:
            self.adapters.release(state.request.adapter)
        self._release_slot(slot, now)

    def _release_slot(self, slot: int, now: float) -> None:
        """Clear one slot's grid state (the shared tail of retirement and
        eviction — block ownership is the caller's concern: retirement
        frees, eviction hands the blocks to the evicted record)."""
        self._slots[slot] = None
        self._active[slot] = False
        self._seq_lens[slot] = 0
        self._last_tokens[slot] = 0
        self._block_tables[slot] = 0
        self._adapter_ids[slot] = 0
        self._dirty("block_tables", "seq_lens", "last_tokens", "active",
                    "adapter_ids")
        if self._events is not None:
            self._events.gauge("occupancy", self.occupancy(), t_ms=now)

    # -- live-slot eviction (the migration primitive) ----------------------
    def evict_slot(self, uid: str) -> Dict[str, Any]:
        """Extract a LIVE decoding slot's full state and free the slot —
        the request is neither retired nor forgotten, it is *portable*:
        :meth:`restore_slot` (here or on another engine with the same
        model/kv config, after its pool blocks were shipped) resumes the
        stream bitwise where it stopped, because everything the decode
        program reads is in the record: the written-context length
        (``seq_len``), the next token to feed (``last_token``), the
        request (whose seed reproduces the sampling key), and the block
        ids holding the K/V.

        The record OWNS the listed blocks: they stay allocated (and
        refcounted — shared prefix-cache blocks are safe to read) until
        the caller either restores the slot locally or, after extracting
        their contents for the wire, releases them with
        ``engine.allocator.free(record["blocks"])``.

        Only fully-prefilled slots are evictable — a mid-prefill slot
        has no resumable decode state yet (its prompt is host-side;
        re-enqueue the request instead)."""
        for slot, state in enumerate(self._slots):
            if state is not None and state.request.uid == uid:
                break
        else:
            raise KeyError(f"no occupied slot holds request {uid!r}")
        if state.prefill_pos < state.prompt_len or not self._active[slot]:
            raise RuntimeError(
                f"{uid}: slot is mid-prefill — only decoding slots are "
                f"evictable (re-enqueue the request instead)")
        record: Dict[str, Any] = {
            "request": state.request,
            "blocks": list(state.blocks),
            "generated": list(state.generated),
            "history": list(state.history),
            "prompt_len": state.prompt_len,
            "cached_tokens": state.cached_tokens,
            "seq_len": int(self._seq_lens[slot]),
            "last_token": int(self._last_tokens[slot]),
            "t_submit_ms": state.t_submit_ms,
            "t_first_ms": state.t_first_ms,
            "queue_ms": state.queue_ms,
            "ttft_ms": state.ttft_ms,
            # the adapter BINDING travels with the KV blocks: the name
            # (per-worker slot ids don't survive migration) — the restore
            # target re-resolves it against ITS registry
            "adapter": state.request.adapter,
        }
        if state.adapter_id and state.request.adapter is not None:
            self.adapters.release(state.request.adapter)
        self._release_slot(slot, self._now_ms())
        return record

    def restore_slot(self, record: Dict[str, Any],
                     blocks: Optional[List[int]] = None) -> int:
        """Re-install an :meth:`evict_slot` record into a free slot.
        ``blocks=None`` reuses the record's own block ids (local evict +
        restore is bitwise a no-op — the pool never moved); a migration
        destination passes the freshly allocated ids its ``insert``
        program landed the transferred blocks in. Returns the slot
        index; raises when no slot is free (callers check capacity
        first — this is an installation primitive, not an admission
        queue)."""
        slot = self._free_slot()
        if slot is None:
            raise RuntimeError(
                f"{record['request'].uid}: no free slot to restore into")
        blocks = list(record["blocks"] if blocks is None else blocks)
        now = self._now_ms()
        aname = record.get("adapter")
        aid = 0
        if aname is not None:
            if self.adapters is None:
                raise RuntimeError(
                    f"{record['request'].uid}: record is bound to adapter "
                    f"{aname!r} but this engine has adapters disabled")
            aid = self.adapters.acquire(aname)
            if aid is None:
                raise RuntimeError(
                    f"{record['request'].uid}: adapter {aname!r} is not "
                    f"resident on the restore target — load_adapter() it "
                    f"before restoring (the cluster's adapter_load path)")
        state = _SlotState(
            request=record["request"], blocks=blocks,
            generated=list(record["generated"]),
            history=list(record["history"]),
            prompt_len=record["prompt_len"],
            prefill_pos=record["prompt_len"],
            cached_tokens=record.get("cached_tokens", 0),
            pending_commits=[],
            t_submit_ms=record["t_submit_ms"],
            t_first_ms=record["t_first_ms"],
            queue_ms=record["queue_ms"], ttft_ms=record["ttft_ms"],
            chunk_start_ms=now, chunk_done=len(record["generated"]),
            adapter_id=aid)
        self._slots[slot] = state
        row = np.zeros((self._blocks_per_slot,), np.int32)
        row[:len(blocks)] = blocks
        self._block_tables[slot] = row
        self._keys[slot] = np.asarray(
            request_key(self._base_key, record["request"].sampling_seed()),
            np.uint32)
        self._seq_lens[slot] = record["seq_len"]
        self._last_tokens[slot] = record["last_token"]
        self._active[slot] = True
        self._adapter_ids[slot] = aid
        self._dirty("block_tables", "keys", "seq_lens", "last_tokens",
                    "active", "adapter_ids")
        if self._t_start is None:
            self._t_start = time.perf_counter()
        if self._events is not None:
            self._events.gauge("occupancy", self.occupancy(), t_ms=now)
        return slot

    # -- adapter lifecycle -------------------------------------------------
    def load_adapter(self, name: str, weights: Dict[str, Any], *,
                     scale: float = 1.0) -> int:
        """Install (or refresh) a named LoRA adapter into the paged pool.
        Host-side eager writes into the donated pool leaves — loading an
        adapter never traces, so compile counts stay flat no matter how
        many tenants churn through. Under pool pressure the registry
        evicts the least-recently-used IDLE adapter (refcount 0); loading
        while every slot is pinned by a decoding request raises. Returns
        the pool slot the adapter landed in."""
        if self.adapters is None:
            raise RuntimeError(
                "adapters are disabled (ServeConfig.lora_rank == 0) — "
                "construct the engine with lora_rank > 0 to load adapters")
        t0 = time.perf_counter()
        slot = self.adapters.load(name)
        self._lora_pool = write_adapter(self._lora_pool, slot, weights,
                                        scale=scale)
        ms = (time.perf_counter() - t0) * 1e3
        self._adapter_load_ms_total += ms
        if self._meter is not None:
            # install time precedes any tenant binding — the _fleet
            # pseudo-tenant pays (a per-tenant amortization would guess)
            self._meter.charge("_fleet", worker=self._meter_worker,
                               adapter_load_ms=ms)
        if self._events is not None:
            self._events.emit("adapter_load", name, slot=slot,
                              load_ms=round(ms, 3))
        return slot

    def unload_adapter(self, name: str) -> None:
        """Drop a named adapter from the pool (must be idle — refcount 0).
        The pool slot's weights are left in place and overwritten by the
        next load; correctness never reads a free slot (per-slot
        adapter-id rows only ever point at resident adapters)."""
        if self.adapters is None:
            raise RuntimeError("adapters are disabled")
        self.adapters.unload(name)
        if self._events is not None:
            self._events.emit("adapter_unload", name)

    # -- speculative drafting ---------------------------------------------
    def _collect_drafts(self) -> Optional[Dict[int, List[int]]]:
        """Ask the drafter for up to spec_k tokens per active slot, capped
        so fed positions stay inside the slot's allocated blocks, the
        context window, and the remaining generation budget. None when no
        slot proposes (the step falls back to the plain decode program)."""
        if self.drafter is None:
            return None
        out: Dict[int, List[int]] = {}
        any_drafts = False
        for i, state in enumerate(self._slots):
            if state is None or not self._active[i]:
                continue
            s = int(self._seq_lens[i])
            remaining = state.request.max_new_tokens - len(state.generated)
            cap = min(
                self.serve_cfg.spec_k,
                remaining - 1,  # the last token is never fed back
                len(state.blocks) * self.kv_cfg.block_size - 1 - s,
                self.max_context - 1 - s,
            )
            if cap < 1:
                continue
            drafts = list(self.drafter.propose(state.history, cap))[:cap]
            if drafts:
                out[i] = [int(t) for t in drafts]
                any_drafts = True
        return out if any_drafts else None

    # -- stepping ----------------------------------------------------------
    def step(self) -> bool:
        """Admit what fits, run one prefill chunk if any prompt is mid-
        prefill, then advance every decode-ready slot — one token via the
        decode program, or up to spec_k+1 via the speculative verify
        program when the drafter proposed. Returns False when nothing
        happened (no admission, no prefill, no active slots). An
        admission-time shed (unknown adapter popped via ``on_reject``)
        counts as progress: the queue moved, even though no slot did —
        otherwise ``run()`` would misread the step as a pool stall."""
        shed0 = self._rejected
        with span("prefill.admit"):
            admitted = self._try_admit()
        chunked = self._run_prefill_chunk()
        if not self._active.any():
            if self._sink is not None and chunked:
                self._sink.write(step=self._step_idx,
                                 phase="prefill_chunk",
                                 prefill_backlog_tokens=(
                                     self._prefill_backlog_tokens()))
            if chunked:
                self._step_idx += 1
            return admitted > 0 or chunked or self._rejected > shed0
        t0 = time.perf_counter()
        drafts = self._collect_drafts()
        with span("decode"):
            with span("decode.dispatch"):
                if drafts is None:
                    self._decode_steps += 1
                    if self._lora_pool is None:
                        self.cache, toks, metrics = self._decode(
                            self.params, self.cache,
                            self._dev("last_tokens"), self._dev("seq_lens"),
                            self._dev("active"), self._dev("block_tables"),
                            self._dev("keys"))
                    else:
                        (self.cache, self._lora_pool, toks,
                         metrics) = self._decode(
                            self.params, self.cache, self._lora_pool,
                            self._dev("last_tokens"), self._dev("seq_lens"),
                            self._dev("active"), self._dev("block_tables"),
                            self._dev("keys"), self._dev("adapter_ids"))
                else:
                    self._verify_steps += 1
                    k1 = self.serve_cfg.spec_k + 1
                    n = self.serve_cfg.num_slots
                    fed = np.zeros((n, k1), np.int32)
                    fed[:, 0] = self._last_tokens
                    n_fed = np.where(self._active, 1, 0).astype(np.int32)
                    for i, d in drafts.items():
                        fed[i, 1:1 + len(d)] = d
                        n_fed[i] = 1 + len(d)
                    if self._lora_pool is None:
                        self.cache, toks, metrics = self._verify(
                            self.params, self.cache, jnp.asarray(fed),
                            self._dev("seq_lens"), jnp.asarray(n_fed),
                            self._dev("active"), self._dev("block_tables"),
                            self._dev("keys"))
                    else:
                        (self.cache, self._lora_pool, toks,
                         metrics) = self._verify(
                            self.params, self.cache, self._lora_pool,
                            jnp.asarray(fed), self._dev("seq_lens"),
                            jnp.asarray(n_fed), self._dev("active"),
                            self._dev("block_tables"), self._dev("keys"),
                            self._dev("adapter_ids"))
            with span("decode.fence"):
                toks = np.asarray(toks)  # the iteration-level sync
        dt = time.perf_counter() - t0
        with span("decode.retire"):
            self.hists["decode_step_ms"].add([dt * 1e3])
            if drafts is not None:
                # the verify A/B's own latency dimension — spec steps also
                # land in decode_step_ms (one engine iteration either way)
                self.hists["verify_step_ms"].add([dt * 1e3])
            now_ms = self._now_ms()
            active_lens = [int(s) + 1 for s, a
                           in zip(self._seq_lens, self._active) if a]
            # tokens FED through the program per active slot (the write/flops
            # unit: a verify step feeds 1 + len(drafts) per slot)
            fed_counts = [1 + len(drafts.get(i, [])) if drafts is not None
                          else 1
                          for i in range(len(self._slots)) if self._active[i]]
            n_active = len(active_lens)
            step_proposed = step_accepted = step_emitted = 0
            for i in range(len(self._slots)):
                if not self._active[i]:
                    continue
                state = self._slots[i]
                if drafts is None:
                    emitted = [int(toks[i])]
                else:
                    d = drafts.get(i, [])
                    step_proposed += len(d)
                    a = 1
                    while a <= len(d) and int(toks[i, a - 1]) == d[a - 1]:
                        a += 1
                    emitted = [int(toks[i, j]) for j in range(a)]
                    step_accepted += a - 1
                retired = False
                n_emit = 0
                for tok in emitted:
                    state.generated.append(tok)
                    state.history.append(tok)
                    self._tokens_generated += 1
                    n_emit += 1
                    if self._should_retire(state, tok):
                        retired = True
                        break
                step_emitted += n_emit
                self._seq_lens[i] += n_emit
                self._last_tokens[i] = state.generated[-1]
                if (self._events is not None and not retired
                        and len(state.generated) - state.chunk_done
                        >= self._chunk_tokens):
                    self._events.emit(
                        "decode_chunk", state.request.uid, t_ms=now_ms,
                        slot=i, start_ms=round(state.chunk_start_ms, 3),
                        n_tokens=len(state.generated) - state.chunk_done)
                    state.chunk_start_ms = now_ms
                    state.chunk_done = len(state.generated)
                if retired:
                    self._retire(i)
            self._dirty("seq_lens", "last_tokens")
            self._spec_proposed += step_proposed
            self._spec_accepted += step_accepted
            self._step_idx += 1
            self._emit_metrics(metrics, dt, n_active, active_lens, fed_counts,
                               step_proposed, step_accepted, step_emitted)
            return True

    def _emit_metrics(self, metrics: Metrics, dt: float, n_active: int,
                      active_lens: List[int], fed_counts: List[int],
                      step_proposed: int, step_accepted: int,
                      step_emitted: int) -> None:
        if self._sink is None:
            return
        # a verify step feeds (writes K/V for, and gathers context per)
        # 1+len(drafts) tokens per slot and emits 1+accepted — the record
        # must not read 1/slot on exactly the steps speculation
        # accelerates
        flops = sum(f * decode_flops_per_token(
            self._n_params, self.cfg.num_layers, self.cfg.hidden, s)
            for s, f in zip(active_lens, fed_counts))
        fed_total = sum(fed_counts)
        read_lens = [s for s, f in zip(active_lens, fed_counts)
                     for _ in range(f)]  # one gather per FED row
        rec = {
            "phase": "decode",
            "step_ms": round(dt * 1e3, 3),
            "occupancy": n_active / self.serve_cfg.num_slots,
            "tokens_per_s": round(step_emitted / dt, 3) if dt else 0.0,
            "kv_read_bytes": kv_read_bytes(self.kv_cfg, read_lens),
            "kv_write_bytes": fed_total * kv_write_bytes_per_token(
                self.kv_cfg),
            "decode_flops_modeled": flops,
            # throughput-optimization telemetry (per-step + cumulative;
            # monitor.view aggregates these)
            "prefill_backlog_tokens": self._prefill_backlog_tokens(),
            "spec_proposed": step_proposed,
            "spec_accepted": step_accepted,
            "prefix_blocks_hit_total": self._prefix_blocks_hit,
            "prefix_blocks_needed_total": self._prefix_blocks_needed,
            "prefill_flops_saved_total": self._prefill_flops_saved,
        }
        if self._peak:
            rec["decode_mfu"] = (flops / dt) / self._peak if dt else 0.0
        self._sink.write(step=self._step_idx, metrics=metrics, **rec)

    # -- driving -----------------------------------------------------------
    def run(self, requests: Sequence[Request],
            max_steps: Optional[int] = None) -> Dict[str, List[int]]:
        """Serve ``requests`` to completion; returns uid -> generated
        tokens (the per-request streams, admission-order-invariant)."""
        for r in requests:
            self.submit(r)
        steps = 0
        while self._pending or self._active.any() or self._prefill_queue:
            if max_steps is not None and steps >= max_steps:
                break
            if not self.step():
                request = self._pending[0][0]
                state_blocks = self.kv_cfg.blocks_for_tokens(
                    self._total_tokens(request))
                if self._on_reject is not None:
                    # structured rejection instead of the deadlock-loud
                    # raise: drop the unservable head and keep serving —
                    # the caller (e.g. the cluster router) decides what a
                    # rejection means
                    self._pending.popleft()
                    self._rejected += 1
                    self._on_reject(request, {
                        "reason": "pool_exhausted",
                        "needed_blocks": state_blocks,
                        "free_blocks": self.allocator.free_count,
                        "pool_blocks": self.kv_cfg.num_blocks,
                    })
                    if self._events is not None:
                        self._events.emit("shed", request.uid,
                                          reason="pool_exhausted")
                    continue
                raise RuntimeError(
                    f"engine stalled: next request needs {state_blocks} "
                    f"blocks, pool has {self.allocator.free_count} free "
                    f"and no active slot will release more — the pool is "
                    f"too small for this request")
            steps += 1
        return dict(self._finished)

    # -- introspection / stats --------------------------------------------
    @property
    def finished(self) -> Dict[str, List[int]]:
        return dict(self._finished)

    @property
    def completed(self) -> int:
        """Requests retired so far (counts even when streams are not
        retained)."""
        return self._completed

    def per_request_state_count(self) -> int:
        """Per-request entries the engine is holding: retained streams +
        queued submissions + occupied slots. With ``retain_streams=False``
        this is O(slots + backlog) forever — the leak gate
        ``tests/test_serve.py`` pins after 10× slot-count requests."""
        return (len(self._finished) + len(self._pending)
                + sum(s is not None for s in self._slots))

    def stats(self) -> Dict[str, Any]:
        """One JSON-serializable telemetry snapshot: counts, latency
        quantiles (p50/p99 from the streaming histograms — bounded
        relative error, O(1) memory), full histogram dumps, the
        prefix-cache / chunked-prefill / speculative-decoding counters,
        and the goodput-under-SLO report when an ``SloSpec`` was given."""
        out: Dict[str, Any] = {
            "completed": self._completed,
            "rejected": self._rejected,
            "steps": self._step_idx,
            "generated_tokens": self._tokens_generated,
            "queue_depth": len(self._pending),
            "occupancy": self.occupancy(),
        }
        tput = self.throughput()
        out["tokens_per_s"] = round(tput, 3) if tput else None
        for name in _HIST_NAMES:
            h = self.hists[name]
            if h.total == 0:
                continue
            out[f"{name}_p50"] = round(h.quantile(0.5), 3)
            out[f"{name}_p99"] = round(h.quantile(0.99), 3)
        # tier-4 forensics: per-component latency attribution (flat keys,
        # lower-better under regress) + the plane's own coverage
        for c, h in self._attrib_hists.items():
            if h.total == 0:
                continue
            out[f"{c}_component_ms_p50"] = round(h.quantile(0.5), 3)
            out[f"{c}_component_ms_p99"] = round(h.quantile(0.99), 3)
        if self._completed:
            out["attrib_coverage"] = round(
                self._attrib_n / self._completed, 4)
        if self._meter is not None:
            m = self._meter.stats(completed=self._completed)
            out["meter"] = m
            out["cost_per_token"] = m["cost_per_token"]
            out["cost_per_request"] = m["cost_per_request"]
            out["meter_coverage"] = m["meter_coverage"]
        out["prefix_cache"] = {
            "enabled": self.serve_cfg.prefix_cache,
            "blocks_hit": self._prefix_blocks_hit,
            "blocks_needed": self._prefix_blocks_needed,
            "hit_rate": round(
                self._prefix_blocks_hit / self._prefix_blocks_needed, 4)
            if self._prefix_blocks_needed else None,
            "tokens_saved": self._prefill_tokens_saved,
            "prefill_flops_saved": self._prefill_flops_saved,
            "cow_copies": self._cow_copies,
            "cached_blocks": self.allocator.cached_count,
            "evictions": self.allocator.blocks_evicted_total,
        }
        out["megakernel"] = self._megakernel
        out["decode_kernel"] = self.decode_kernel
        out["verify_kernel"] = self.verify_kernel
        # the sub-8-bit KV headline fields (watcher-gated: kv_bits and
        # the budget are lower-better, contexts_max higher-better)
        out["kv_bits"] = (self.kv_cfg.bits if self.kv_cfg.quantized
                          else 8 * jnp.dtype(self.kv_cfg.dtype).itemsize)
        out["kv_cache_bytes"] = kv_cache_bytes(self.kv_cfg)
        out["contexts_max"] = (self.kv_cfg.tokens_capacity
                               // self.max_context)
        out["prefill"] = {
            "chunk": self.serve_cfg.prefill_chunk,
            "chunks_run": self._chunks_run,
            "backlog_tokens": self._prefill_backlog_tokens(),
        }
        out["speculative"] = {
            "k": self.serve_cfg.spec_k,
            "proposed": self._spec_proposed,
            "accepted": self._spec_accepted,
            "acceptance_rate": round(
                self._spec_accepted / self._spec_proposed, 4)
            if self._spec_proposed else None,
            "verify_steps": self._verify_steps,
            "decode_steps": self._decode_steps,
        }
        if self.adapters is not None:
            a = self.adapters
            lookups = a.hits_total + a.misses_total
            out["adapters"] = {
                "rank": self.serve_cfg.lora_rank,
                "max_adapters": self.serve_cfg.max_adapters,
                "resident": a.resident_count,
                "pool_bytes": adapter_pool_bytes(
                    self.cfg, self.serve_cfg.lora_rank,
                    self.serve_cfg.max_adapters),
                "hits": a.hits_total,
                "misses": a.misses_total,
                "loads": a.loads_total,
                "unloads": a.unloads_total,
                "evictions": a.evictions_total,
            }
            # flat watcher-gated fields: hit rate higher-better,
            # load latency and eviction churn lower-better
            out["adapter_hit_rate"] = (
                round(a.hits_total / lookups, 4) if lookups else None)
            out["adapter_evictions"] = a.evictions_total
            out["adapter_load_ms"] = round(self._adapter_load_ms_total, 3)
        # flat aliases for regression gating (monitor.regress flattens
        # dotted keys; these are the two headline rates)
        out["prefix_hit_rate"] = out["prefix_cache"]["hit_rate"]
        out["spec_acceptance_rate"] = out["speculative"]["acceptance_rate"]
        # model-parallel serving fields (serve.sharded engines only):
        # plan (the residency story), hbm_model_bytes (unsharded "does
        # it fit one chip" numerator), weight_gather_ms /
        # pp_bubble_fraction (strategy-specific, lower-better under
        # monitor.regress)
        if self.plan_stats is not None:
            out.update(self.plan_stats())
        out["hists"] = {k: v.to_dict() for k, v in self.hists.items()}
        if self._slo is not None:
            out["slo_report"] = self._slo.report()
        return out

    # -- fleet exposition (monitor tier 3) --------------------------------
    def collect_registry(self, reg, worker: str = "engine",
                         t_ms: Optional[float] = None,
                         include_hists: bool = False) -> None:
        """Populate a :class:`~apex_tpu.monitor.registry.MetricsRegistry`
        with this engine's live series, labeled ``worker=``. Counters
        are cumulative-at-scrape (the Prometheus pull model: the fleet
        view sums across WORKERS, never across time); ``include_hists``
        additionally snapshots the latency histograms (skipped on the
        per-tick scrape cadence — quantile merges belong to stats())."""
        if t_ms is None:
            t_ms = self._now_ms()
        L = {"worker": worker}
        reg.gauge("worker_up", 1.0, t_ms=t_ms, **L)
        reg.counter("requests_completed_total", self._completed, **L)
        reg.counter("requests_rejected_total", self._rejected, **L)
        reg.counter("tokens_generated_total", self._tokens_generated, **L)
        reg.counter("decode_steps_total",
                    self._decode_steps + self._verify_steps, **L)
        reg.gauge("occupancy", self.occupancy(), t_ms=t_ms, **L)
        reg.gauge("queue_depth", float(len(self._pending)), t_ms=t_ms, **L)
        reg.gauge("backlog_tokens", float(self._prefill_backlog_tokens()),
                  t_ms=t_ms, **L)
        if self._slo is not None:
            reg.counter("slo_good_total", self._slo.good, **L)
        if self.adapters is not None:
            reg.gauge("adapters_resident", float(
                self.adapters.resident_count), t_ms=t_ms, **L)
            reg.counter("adapter_hits_total", self.adapters.hits_total, **L)
            reg.counter("adapter_misses_total",
                        self.adapters.misses_total, **L)
            reg.counter("adapter_loads_total",
                        self.adapters.loads_total, **L)
            reg.counter("adapter_evictions_total",
                        self.adapters.evictions_total, **L)
        if include_hists:
            for name, h in self.hists.items():
                reg.set_histogram(name, h, **L)

    def scrape(self, worker: str = "engine",
               t_ms: Optional[float] = None,
               include_hists: bool = False) -> Dict[str, Any]:
        """One :class:`~apex_tpu.monitor.registry.MetricsRegistry`
        snapshot of this engine (what a ``FleetScraper`` target
        returns; ``MetricsRegistry.expose_text`` of the same registry
        is the Prometheus text endpoint)."""
        from apex_tpu.monitor.registry import MetricsRegistry

        reg = MetricsRegistry()
        if t_ms is None:
            t_ms = self._now_ms()
        self.collect_registry(reg, worker=worker, t_ms=t_ms,
                              include_hists=include_hists)
        return reg.snapshot(t_ms)

    @property
    def active(self) -> bool:
        """Whether the engine still has work: a slot mid-generation or
        mid-prefill, or a queued submission (the drive-loop condition
        loadgen polls)."""
        return (bool(self._active.any()) or bool(self._pending)
                or bool(self._prefill_queue))

    def occupancy(self) -> float:
        """Occupied slots (decoding or mid-prefill) / total slots."""
        return (sum(s is not None for s in self._slots)
                / self.serve_cfg.num_slots)

    def throughput(self) -> Optional[float]:
        """Generated tokens per second since the first token."""
        if self._t_start is None:
            return None
        dt = time.perf_counter() - self._t_start
        return self._tokens_generated / dt if dt > 0 else None

    def kv_budget_bytes(self) -> int:
        return kv_cache_bytes(self.kv_cfg)

    # -- checkpoint integration -------------------------------------------
    @classmethod
    def from_checkpoint(cls, directory: str, template_params: Pytree, cfg,
                        serve_cfg: Optional[ServeConfig] = None,
                        **kwargs) -> "InferenceEngine":
        """Build an engine from the newest VALID checkpoint under
        ``directory`` (``resilience.CheckpointManager.latest_valid`` —
        torn/corrupt saves are skipped, a wrong-revision manifest refuses
        to bind). ``template_params`` supplies the pytree structure (e.g.
        ``init_gpt_params`` output)."""
        from apex_tpu.resilience.checkpoint import CheckpointManager

        mgr = CheckpointManager(directory)
        params, step = mgr.restore(template_params)
        eng = cls(params, cfg, serve_cfg, **kwargs)
        eng.checkpoint_step = step
        return eng


def decode_flops_per_token(n_params: int, num_layers: int, hidden: int,
                           context: int) -> float:
    """Modeled forward flops to decode ONE token at the given context:
    ``2N`` matmul flops plus paged attention ``4·L·hidden·context`` (QKᵀ
    and PV against the cached context). The serving analogue of
    ``monitor.report.gpt_analytic_flops_per_token`` (which counts fwd+bwd
    at 6N): a serving MFU divided by this is honest about being a
    model."""
    return float(2 * n_params + 4 * num_layers * hidden * context)
