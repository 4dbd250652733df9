"""Plan-driven model-parallel serving — one :class:`ParallelismPlan` from
training to pod-scale inference.

The single-chip engine caps the servable model at one chip's HBM. This
module lifts that: :func:`build_engine` reads ``ServeConfig.plan`` (the
SAME frozen plan object a train step is configured by) and builds an
:class:`~apex_tpu.serve.engine.InferenceEngine` whose programs run one of
three residency strategies on a mesh slice:

``tp`` (``ParallelismPlan(tp=N)``)
    Megatron weight shards, one engine, ``shard_map``-wrapped programs.
    The q_len>1 paths (chunked prefill, spec verify) route their
    row-parallel exits through the ``comm.overlap`` rings when the plan
    sets ``overlap_comm`` — partial GEMMs hide the hops, provable from
    compiled HLO via ``analyze.collectives.overlap_assertion`` on
    :func:`program_hlo`. q_len=1 decode stays monolithic (the PR-5 pin: a
    single-row GEMM has nothing to hide a hop behind). Numerics: psum
    ring association ⇒ logits equal up to fp reorder; the greedy/sampled
    token STREAMS still match the oracle at test tolerances.

``pp`` (``ParallelismPlan(pp=S)``)
    :class:`PPStagedEngine`: each stage holds ``num_layers/S`` layers and
    the KV pools for exactly those layers (same block ids, one shared
    host allocator), committed to its own device. Activations — not KV
    blocks — stream between stages; decode/verify split the slot grid
    into microbatches and drive a 1F tick loop with a bounded per-stage
    handoff window (the cluster backpressure-credit idea applied to
    activations). ``stats()`` reports the measured
    ``pp_bubble_fraction`` next to the (S-1)/(M+S-1) model. Numerics:
    splitting the layer scan changes no op order ⇒ BITWISE vs the
    oracle.

``fsdp`` (``ParallelismPlan("fsdp")``)
    Weight residency: per-layer block-aligned flat shards stay resident
    ((L, k) leaves, model dtype); each scan step gathers exactly one
    layer's full weights through the stateless ``FSDP.gather_leaf``
    VJP-forward (inference carries no EF state — the plan validates
    those knobs away) and drops them with the scan step. The
    ``weight_gather`` codec (int8/int4) halves/quarters the gather wire
    bytes; ``stats()`` reports measured ``weight_gather_ms`` and the
    modeled wire bytes. Embed/head stay replicated: every step embeds
    and samples, and a per-step vocab-table gather would dominate the
    ring. Numerics: uncompressed gather is slice-concat reconstruction ⇒
    BITWISE; a codec trades exactness for wire bytes (opt-in).

``fsdp/accounting.hbm_serve_bytes`` prices all three against a chip
budget before anything compiles — the bench headline is a model whose
``hbm_model_bytes`` EXCEEDS one chip served under SLO from the slice.
"""

from __future__ import annotations

import collections
import dataclasses
import inspect
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.fsdp import accounting as _acct
from apex_tpu.fsdp.core import FSDP, LeafMeta
from apex_tpu.contrib.optimizers._sharding import slice_leaf
from apex_tpu.parallel.mesh import TP_AXIS
from apex_tpu.serve.decode import (
    _embed,
    paged_layer_stack,
    serve_logits,
)
from apex_tpu.serve.engine import InferenceEngine
from apex_tpu.serve.kv_cache import (
    copy_block,
    init_kv_cache,
    kv_cache_bytes,
)
from apex_tpu.serve.sampling import sample
from apex_tpu.monitor.metrics import Metrics
from apex_tpu.transformer.testing.standalone_gpt import gpt_param_specs

from jax.sharding import NamedSharding, PartitionSpec as P


Pytree = Any

__all__ = [
    "build_engine",
    "PPStagedEngine",
    "plan_world",
    "program_hlo",
    "tp_transform",
]


# ---------------------------------------------------------------------------
# shared plumbing


def plan_world(plan, devices: Optional[Sequence[Any]] = None) -> int:
    """Chip count the plan's serve strategy spans — ``tp``/``pp`` read
    their own degree; ``fsdp`` reads ``dp`` (-1 = every device given)."""
    s = plan.serve_strategy()
    if s == "tp":
        return plan.tp
    if s == "pp":
        return plan.pp
    if plan.dp > 0:
        return plan.dp
    return len(devices) if devices is not None else len(jax.devices())


def _apply_overrides(cfg, plan):
    if not hasattr(plan, "serve_overrides"):
        # the ServeConfig.validate() message, raised here too so
        # build_engine(plan="tp") dies loudly instead of AttributeError
        raise ValueError(f"plan must be a ParallelismPlan "
                         f"(apex_tpu.parallel.plan), got {type(plan)!r}")
    ov = plan.serve_overrides()
    if cfg.overlap_comm != ov["overlap_comm"]:
        cfg = dataclasses.replace(cfg, overlap_comm=ov["overlap_comm"])
    return cfg, ov


def _in_specs_for(fn: Callable, param_spec, cache_spec) -> Tuple:
    """Positional in_specs for one engine program closure: params get the
    model layout, the cache its pool layout, everything else (tokens,
    lens, tables, keys) is replicated. Keyed by NAME — the engine's
    closures share a fixed argument vocabulary."""
    specs = []
    for nm in inspect.signature(fn).parameters:
        if nm == "params":
            specs.append(param_spec)
        elif nm == "cache":
            specs.append(cache_spec)
        else:
            specs.append(P())
    return tuple(specs)


# out_specs per program closure name: decode/verify -> (cache, toks,
# Metrics), chunk_prefill -> (cache, tok), cow -> cache
def _out_specs_for(name: str, cache_spec):
    return {
        "chunk_prefill": (cache_spec, P()),
        "decode": (cache_spec, P(), P()),
        "verify": (cache_spec, P(), P()),
        "cow": cache_spec,
    }[name]


# ---------------------------------------------------------------------------
# (a) TP serving — Megatron shards under shard_map


def tp_transform(cfg, mesh) -> Callable[[Callable], Callable]:
    """The ``transform=`` for a TP-serving engine: wraps each program in
    ``shard_map`` with ``gpt_param_specs`` on params and heads-sharded
    pools on the cache (every pool leaf — K, V, and the quantized scales
    — carries heads at dim 1, so ONE spec covers them all).
    ``check_vma=False`` is the repo idiom for type-varying ring outputs
    (the overlap exits return psum-reordered, replicated-value arrays)."""
    param_spec = gpt_param_specs(cfg)
    cache_spec = P(None, TP_AXIS)

    def wrap(fn):
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=_in_specs_for(fn, param_spec, cache_spec),
            out_specs=_out_specs_for(fn.__name__, cache_spec),
            check_vma=False)

    return wrap


def _build_tp_engine(params, cfg, serve_cfg, plan, mesh, devices,
                     **engine_kw) -> InferenceEngine:
    tp = plan.tp
    if mesh is None:
        mesh = plan.mesh(devices[:tp] if devices is not None else None)
    engine = InferenceEngine(
        params, cfg, serve_cfg, transform=tp_transform(cfg, mesh),
        tp_axis=TP_AXIS, tp_size=tp, **engine_kw)
    # the engine sized kv_cfg per-CHIP (local heads — its byte accounting
    # and the in-shard_map layer stack both want that view); the GLOBAL
    # pool the jitted programs take holds full heads, sharded by in_specs
    full_kv = dataclasses.replace(engine.kv_cfg,
                                  num_heads=cfg.num_heads)
    # place params and pool in their STEADY-STATE layouts up front — the
    # first program call otherwise sees single-device inputs, returns
    # mesh-sharded outputs, and the layout flip costs one retrace (the
    # compile-count gate would read 2 where the plain engine reads 1)
    engine.params = jax.device_put(
        params, jax.tree.map(lambda s: NamedSharding(mesh, s),
                             gpt_param_specs(cfg),
                             is_leaf=lambda x: isinstance(x, P)))
    engine.cache = jax.device_put(init_kv_cache(full_kv),
                                  NamedSharding(mesh, P(None, TP_AXIS)))
    model_bytes = _acct.hbm_model_bytes(params)
    chip = _acct.hbm_serve_bytes(
        params, strategy="tp", world=tp,
        kv_bytes=kv_cache_bytes(engine.kv_cfg),
        num_layers=cfg.num_layers)

    def plan_stats() -> Dict[str, Any]:
        return {
            "plan": "tp",
            "plan_world": tp,
            "hbm_model_bytes": model_bytes,
            "hbm_chip_bytes": chip["total"],
        }

    engine.plan_stats = plan_stats
    return engine


# ---------------------------------------------------------------------------
# (c) FSDP weight residency — resident shards, gather-on-demand per layer


def _layer_shard_meta(layers: Dict[str, Any]) -> Dict[str, LeafMeta]:
    """Per-LAYER LeafMeta for each stacked leaf: shape minus the leading
    L axis — what one scan step's gather must reconstruct."""
    return {k: LeafMeta(tuple(jnp.shape(v))[1:], str(jnp.result_type(v)))
            for k, v in layers.items()}


def _build_fsdp_engine(params, cfg, serve_cfg, plan, mesh, devices,
                       **engine_kw) -> InferenceEngine:
    world = plan_world(plan, devices)
    if mesh is None:
        mesh = plan.mesh(devices[:world] if devices is not None else None)
    axis = plan.dp_axis
    fsdp = FSDP(axis_name=axis, weight_gather=plan.weight_gather)
    mult = fsdp.shard_multiple
    layers = params["layers"]
    metas = _layer_shard_meta(layers)

    # one-time resharding program: stacked (L, *rest) -> resident (L, k)
    # model-dtype rows, block-aligned so no codec scale block straddles
    # ranks (bitwise gather when no codec: pad + slice + concat + unpad
    # is the identity)
    def _shard_layers(ls):
        return {
            k: jax.vmap(lambda row: slice_leaf(row, axis, multiple=mult))(v)
            for k, v in ls.items()}

    shard_prog = jax.jit(jax.shard_map(
        _shard_layers, mesh=mesh, in_specs=(P(),),
        out_specs=P(None, axis), check_vma=False))
    shards = shard_prog(layers)
    # embed/head replicas placed mesh-wide up front (same retrace-avoidance
    # as the tp build: first-call layout must already be steady state)
    repl = NamedSharding(mesh, P())
    serve_params = {"embed": jax.device_put(params["embed"], repl),
                    "head": jax.device_put(params["head"], repl),
                    "layers": shards}

    def gather_layer(lp: Dict[str, Any]) -> Dict[str, Any]:
        return {k: fsdp.gather_leaf(v, metas[k]) for k, v in lp.items()}

    param_spec = {"embed": P(), "head": P(), "layers": P(None, axis)}

    def wrap(fn):
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=_in_specs_for(fn, param_spec, P()),
            out_specs=_out_specs_for(fn.__name__, P()),
            check_vma=False)

    engine = InferenceEngine(serve_params, cfg, serve_cfg, transform=wrap,
                             gather_layer=gather_layer, **engine_kw)
    engine.cache = jax.device_put(engine.cache, repl)
    # flops accounting wants the MODEL's parameter count, not the
    # padded resident-shard count
    engine._n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(params))

    # measured full-gather latency: a dedicated program running exactly
    # the per-layer gathers the decode scan runs, timed end to end —
    # lazily, once (compiling it is pointless if stats() never asks)
    def _gather_all(ls):
        return {k: jax.vmap(lambda s: fsdp.gather_leaf(s, metas[k]))(v)
                for k, v in ls.items()}

    gather_prog = jax.jit(jax.shard_map(
        _gather_all, mesh=mesh, in_specs=(P(None, axis),),
        out_specs=P(), check_vma=False))
    measured: Dict[str, float] = {}

    def _measure_gather_ms() -> float:
        if "ms" not in measured:
            jax.block_until_ready(gather_prog(shards))  # compile + warm
            t0 = time.perf_counter()
            jax.block_until_ready(gather_prog(shards))
            measured["ms"] = (time.perf_counter() - t0) * 1e3
        return measured["ms"]

    model_bytes = _acct.hbm_model_bytes(params)
    chip = _acct.hbm_serve_bytes(
        params, strategy="fsdp", world=world,
        kv_bytes=kv_cache_bytes(engine.kv_cfg),
        num_layers=cfg.num_layers, shard_multiple=mult)
    wire = cfg.num_layers * _acct.param_gather_wire_bytes(
        metas, world, plan.weight_gather, mult)

    def plan_stats() -> Dict[str, Any]:
        return {
            "plan": "fsdp",
            "plan_world": world,
            "hbm_model_bytes": model_bytes,
            "hbm_chip_bytes": chip["total"],
            "weight_gather_ms": _measure_gather_ms(),
            "weight_gather_wire_bytes": wire,
        }

    engine.plan_stats = plan_stats
    return engine


# ---------------------------------------------------------------------------
# (b) PP-staged serving — activations stream between layer shards


class PPStagedEngine(InferenceEngine):
    """Pipeline-staged engine: stage s owns layers ``[s·L/S, (s+1)·L/S)``
    and the KV pools for exactly those layers, committed to its own
    device. The public surface is the base engine's — ``submit``/
    ``step``/``run``/``stats`` — but the four programs become host
    drivers over per-stage jitted programs: decode/verify split the slot
    grid into M microbatches and tick a 1F schedule where stage s runs
    microbatch ``t - s``, bounded by a per-stage handoff window (the
    cluster backpressure-credit contract: a stage whose downstream
    buffer is full stalls, and the stall is COUNTED, not hidden).
    Prefill (one prompt) runs straight through — a single chunk cannot
    pipeline against itself, and its S-tick bubble is reported, not
    smoothed over.

    Bitwise vs the single-chip oracle: splitting the layer scan at stage
    boundaries reorders no per-layer op, rows are independent, and
    sampling draws are (request, position)-keyed.
    """

    def __init__(self, params, cfg, serve_cfg, *,
                 devices: Optional[Sequence[Any]] = None,
                 microbatches: Optional[int] = None,
                 stage_window: int = 1,
                 **engine_kw):
        plan = serve_cfg.plan
        if plan is None or plan.serve_strategy() != "pp":
            raise ValueError("PPStagedEngine needs ServeConfig.plan with "
                             "pp > 1 (and nothing else sharding)")
        S = plan.pp
        if cfg.num_layers % S:
            raise ValueError(
                f"pp={S} stages need num_layers ({cfg.num_layers}) "
                f"divisible by the stage count")
        n = serve_cfg.num_slots
        if microbatches is None:
            # largest microbatch count <= S that divides the slot grid:
            # more would add handoffs without shrinking the bubble below
            # (S-1)/(M+S-1)'s knee; fewer wastes overlap
            microbatches = next(m for m in range(min(S, n), 0, -1)
                                if n % m == 0)
        if n % microbatches:
            raise ValueError(
                f"microbatches ({microbatches}) must divide num_slots "
                f"({n}) — ragged microbatches would retrace per step")
        if stage_window < 1:
            raise ValueError(
                f"stage_window must be >= 1, got {stage_window}")
        self._pp_stages = S
        self._pp_mb = microbatches
        self._pp_window = stage_window
        if devices is None:
            devices = jax.devices()
        if len(devices) < S:
            raise ValueError(
                f"pp={S} stages need {S} devices, have {len(devices)}")
        self._pp_devs = list(devices)[:S]
        self._pp_busy_cells = 0
        self._pp_total_cells = 0
        self._pp_credit_waits = 0
        for bad in ("transform", "tp_axis", "tp_size", "gather_layer"):
            if engine_kw.get(bad):
                raise ValueError(f"{bad} is owned by the PP engine")
        super().__init__(params, cfg, serve_cfg, **engine_kw)
        model_bytes = _acct.hbm_model_bytes(params)
        chip = _acct.hbm_serve_bytes(
            params, strategy="pp", world=S,
            kv_bytes=kv_cache_bytes(self._stage_kv),
            num_layers=cfg.num_layers)
        self._pp_chip_bytes = chip["total"]
        self._pp_model_bytes = model_bytes
        # base __init__ pins the instance attr to None; point it at the
        # stage accounting so engine.stats() carries the plan block
        self.plan_stats = self._pp_plan_stats

    # -- program construction ---------------------------------------------
    def _build_programs(self, wrap) -> None:
        cfg, scfg = self.cfg, self.serve_cfg
        S = self._pp_stages
        Ls = cfg.num_layers // S
        self._stage_kv = dataclasses.replace(self.kv_cfg, num_layers=Ls)
        skv = self._stage_kv
        layers = self.params["layers"]
        stage_params: List[Pytree] = []
        for s in range(S):
            pd: Dict[str, Any] = {
                "layers": {k: v[s * Ls:(s + 1) * Ls]
                           for k, v in layers.items()}}
            if s == 0:
                pd["embed"] = self.params["embed"]
            if s == S - 1:
                pd["head"] = self.params["head"]
                # tied logits read the token table; last stage holds a
                # replica either way (embed/head replication is the
                # accounting model's assumption too)
                pd["embed"] = self.params["embed"]
            stage_params.append(jax.device_put(pd, self._pp_devs[s]))
        self.params = stage_params
        # per-stage pools, committed: stage s writes/reads ITS layers
        # under the engine-global block ids and allocator
        self.cache = [jax.device_put(init_kv_cache(skv), d)
                      for d in self._pp_devs]

        def _make_stage(s: int):
            first, last = s == 0, s == S - 1

            def stage_fwd(pd, cache_s, x, start_lens, n_valid, active,
                          block_tables):
                if first:
                    q = x.shape[1]
                    offs = jnp.arange(q)
                    positions = start_lens[:, None] + offs[None, :]
                    positions_c = jnp.minimum(positions, cfg.max_seq - 1)
                    x = _embed(pd["embed"], x, positions_c, None)
                x, cache_s = paged_layer_stack(
                    x, pd["layers"], start_lens, n_valid, active, cache_s,
                    block_tables, cfg, skv, tp_axis=None,
                    use_pallas=self._use_pallas)
                if last:
                    x = serve_logits(pd, x, cfg, None)
                return cache_s, x

            def stage_cow(cache_s, src, dst):
                return copy_block(cache_s, src, dst)

            return (jax.jit(stage_fwd, donate_argnums=(1,)),
                    jax.jit(stage_cow, donate_argnums=(0,)))

        made = [_make_stage(s) for s in range(S)]
        self._stage_fwd = [f for f, _ in made]
        self._stage_cow = [c for _, c in made]
        self._chunk_prefill = self._pp_chunk_prefill
        self._decode = self._pp_decode
        self._verify = self._pp_verify if scfg.spec_k > 0 else None
        self._cow = self._pp_cow

    # -- the pipeline tick loop -------------------------------------------
    def _pp_forward(self, tokens, start_lens, n_valid, active,
                    block_tables, microbatches: int):
        """Drive (n, q) token rows through the stages in ``microbatches``
        row-slices; returns (n, q, vocab) fp32 logits. Stage caches
        update in place (donated per stage call)."""
        S = self._pp_stages
        n = tokens.shape[0]
        nmb = n // microbatches
        ready: List[collections.deque] = [collections.deque()
                                          for _ in range(S)]
        for m in range(microbatches):
            sl = slice(m * nmb, (m + 1) * nmb)
            ready[0].append((m, (tokens[sl], start_lens[sl], n_valid[sl],
                                 active[sl], block_tables[sl])))
        out: List[Any] = [None] * microbatches
        pending = microbatches
        while pending:
            self._pp_total_cells += S
            progressed = False
            # drain downstream first: a handoff produced this tick is
            # consumed next tick — the 1F timing the bubble model prices
            for s in reversed(range(S)):
                if not ready[s]:
                    continue
                if s < S - 1 and len(ready[s + 1]) >= self._pp_window:
                    # backpressure credit exhausted: the downstream
                    # buffer is full, this stage idles the tick
                    self._pp_credit_waits += 1
                    continue
                m, (x, st, nv, ac, bt) = ready[s].popleft()
                if s > 0:  # activation handoff: the inter-stage stream
                    x = jax.device_put(x, self._pp_devs[s])
                cache_s, y = self._stage_fwd[s](
                    self.params[s], self.cache[s], x, st, nv, ac, bt)
                self.cache[s] = cache_s
                self._pp_busy_cells += 1
                progressed = True
                if s == S - 1:
                    out[m] = y
                    pending -= 1
                else:
                    ready[s + 1].append((m, (y, st, nv, ac, bt)))
            if not progressed:  # pragma: no cover - schedule invariant
                raise RuntimeError("pipeline deadlock: no stage ran")
        # host hop: the concat-and-sample epilogue runs on the default
        # device; per-microbatch logits are committed to the last stage
        return jnp.asarray(np.concatenate(
            [np.asarray(o) for o in out], axis=0))

    # -- the four engine programs, as host drivers ------------------------
    def _pp_decode(self, params, cache, last_tokens, seq_lens, active,
                   block_tables, keys):
        del params, cache  # the engine passes them back; stages own them
        n = last_tokens.shape[0]
        logits = self._pp_forward(
            jnp.asarray(last_tokens)[:, None], jnp.asarray(seq_lens),
            jnp.ones((n,), jnp.int32), jnp.asarray(active),
            jnp.asarray(block_tables), self._pp_mb)[:, 0]
        toks = sample(logits, keys, seq_lens + 1, self.serve_cfg.sampling)
        m = Metrics().record(
            active_slots=jnp.sum(active),
            context_tokens=jnp.sum(jnp.where(active, seq_lens + 1, 0)))
        return self.cache, toks, m

    def _pp_verify(self, params, cache, fed_tokens, seq_lens, n_fed,
                   active, block_tables, keys):
        del params, cache
        k1 = fed_tokens.shape[1]
        logits = self._pp_forward(
            jnp.asarray(fed_tokens), jnp.asarray(seq_lens),
            jnp.asarray(n_fed), jnp.asarray(active),
            jnp.asarray(block_tables), self._pp_mb)
        draw_pos = seq_lens[:, None] + 1 + jnp.arange(k1)[None, :]
        toks = sample(logits, keys, draw_pos, self.serve_cfg.sampling)
        m = Metrics().record(
            active_slots=jnp.sum(active),
            context_tokens=jnp.sum(jnp.where(active, seq_lens + 1, 0)))
        return self.cache, toks, m

    def _pp_chunk_prefill(self, params, cache, tokens, start, n_valid,
                          block_row, key):
        del params, cache
        logits = self._pp_forward(
            jnp.asarray(tokens)[None, :], jnp.asarray(start)[None],
            jnp.asarray(n_valid)[None], jnp.ones((1,), bool),
            jnp.asarray(block_row)[None, :], 1)
        last = jnp.take(logits[0], jnp.maximum(jnp.asarray(n_valid) - 1, 0),
                        axis=0)
        tok = sample(last[None], key[None],
                     jnp.reshape(start + n_valid, (1,)),
                     self.serve_cfg.sampling)
        return self.cache, tok[0]

    def _pp_cow(self, cache, src, dst):
        return [cow(c, src, dst)
                for cow, c in zip(self._stage_cow, cache)]

    # -- surfaces ----------------------------------------------------------
    def programs(self) -> Dict[str, Optional[Callable]]:
        progs: Dict[str, Optional[Callable]] = {}
        for s in range(self._pp_stages):
            progs[f"pp_stage{s}"] = self._stage_fwd[s]
            progs[f"pp_cow{s}"] = self._stage_cow[s]
        return progs

    def pp_bubble_fraction(self) -> float:
        """Measured idle fraction of stage·tick cells across every
        pipeline drive so far (0.0 before any)."""
        if not self._pp_total_cells:
            return 0.0
        return 1.0 - self._pp_busy_cells / self._pp_total_cells

    def _pp_plan_stats(self) -> Dict[str, Any]:
        S, M = self._pp_stages, self._pp_mb
        return {
            "plan": "pp",
            "plan_world": S,
            "hbm_model_bytes": self._pp_model_bytes,
            "hbm_chip_bytes": self._pp_chip_bytes,
            "pp_microbatches": M,
            "pp_bubble_fraction": self.pp_bubble_fraction(),
            "pp_bubble_fraction_modeled": (S - 1) / (M + S - 1),
            "pp_credit_waits": self._pp_credit_waits,
        }


# ---------------------------------------------------------------------------
# front door


def build_engine(params, cfg, serve_cfg, *,
                 devices: Optional[Sequence[Any]] = None,
                 mesh=None, **engine_kw) -> InferenceEngine:
    """One constructor for every residency: reads ``serve_cfg.plan`` and
    returns a ready engine — the plain single-chip
    :class:`InferenceEngine` when the plan is None, else the strategy the
    plan's ``serve_overrides()`` resolves (``tp``/``pp``/``fsdp``).

    ``params`` is always the MERGED single-chip checkpoint layout
    (``init_gpt_params`` structure); resharding into the plan's resident
    layout happens here, on device. ``devices`` defaults to
    ``jax.devices()`` — the first ``plan_world(plan)`` of them form the
    slice. ``mesh`` overrides the plan-built mesh (tp/fsdp only).
    """
    plan = serve_cfg.plan
    if plan is None:
        return InferenceEngine(params, cfg, serve_cfg, **engine_kw)
    cfg, ov = _apply_overrides(cfg, plan)
    strategy = ov["strategy"]
    devs = list(devices) if devices is not None else None
    if strategy == "tp":
        return _build_tp_engine(params, cfg, serve_cfg, plan, mesh, devs,
                                **engine_kw)
    if strategy == "fsdp":
        return _build_fsdp_engine(params, cfg, serve_cfg, plan, mesh,
                                  devs, **engine_kw)
    return PPStagedEngine(params, cfg, serve_cfg, devices=devs,
                          **engine_kw)


def program_hlo(engine: InferenceEngine, name: str = "verify") -> str:
    """Compiled HLO text of one engine program, lowered at the engine's
    own shapes — feed ``analyze.collectives.overlap_assertion`` /
    ``assert_no_exposed`` to PROVE the q_len>1 TP exits hide their ring
    hops behind partial GEMMs (the acceptance gate), instead of trusting
    the flag. Lowers out-of-band: the engine's jit caches see nothing."""
    progs = engine.programs()
    if name not in progs or progs[name] is None:
        raise ValueError(
            f"engine has no program {name!r} (have "
            f"{[k for k, v in progs.items() if v is not None]})")
    scfg = engine.serve_cfg
    n = scfg.num_slots
    bps = engine._blocks_per_slot
    i32, u32 = jnp.int32, jnp.uint32
    if name == "chunk_prefill":
        args = (engine.params, engine.cache,
                jnp.zeros((scfg.prefill_chunk,), i32), i32(0), i32(1),
                jnp.zeros((bps,), i32), jnp.zeros((2,), u32))
    elif name == "decode":
        args = (engine.params, engine.cache, jnp.zeros((n,), i32),
                jnp.zeros((n,), i32), jnp.zeros((n,), bool),
                jnp.zeros((n, bps), i32), jnp.zeros((n, 2), u32))
    elif name == "verify":
        args = (engine.params, engine.cache,
                jnp.zeros((n, scfg.spec_k + 1), i32),
                jnp.zeros((n,), i32), jnp.ones((n,), i32),
                jnp.zeros((n,), bool), jnp.zeros((n, bps), i32),
                jnp.zeros((n, 2), u32))
    else:
        raise ValueError(f"no dummy-arg recipe for program {name!r}")
    return progs[name].lower(*args).compile().as_text()
