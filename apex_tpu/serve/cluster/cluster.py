"""ServeCluster — the disaggregated prefill/decode step loop.

One object wires the whole multi-host story together: an SLO-aware
:class:`~apex_tpu.serve.cluster.router.Router` in front, ``n_prefill``
:class:`~apex_tpu.serve.cluster.workers.PrefillWorker` hosts feeding a
:class:`~apex_tpu.serve.cluster.transfer.SimTransport` (or a real ICI
link built from the same payloads), and ``n_decode``
:class:`~apex_tpu.serve.cluster.workers.DecodeWorker` hosts draining it.
Every :meth:`ServeCluster.step` is one cluster tick:

    chaos plan → preemption/heartbeat/watchdog checks → deliver
    transfers (CRC-validated; corrupt/late ones retried with backoff) →
    router dispatch (WFQ + TTFT feasibility, sheds are terminal) → one
    prefill chunk per busy prefill host → ship finished prefills →
    admit + one decode step per ALIVE decode host

All timestamps come from ONE :class:`~apex_tpu.monitor.events.EventLog`
clock shared by the router, both worker kinds, the membership ledger
and every decode engine, so the request lifecycle — ``submitted →
prefill_start/end → first_token → transfer_start/end → admitted →
decode_chunk* → retired`` (or ``submitted → shed``) — lines up across
hosts in the JSONL stream and the Chrome trace, and so do the elastic
events: ``worker_join`` / ``worker_leave``, ``migrate_start →
migrate_end`` spans when a request hops off a dying host, ``replay``
when its unacked tail is re-emitted.

**The elastic tier** (ROADMAP item 3): the dispatch set is a runtime
quantity. Workers join and leave through a
:class:`~apex_tpu.serve.cluster.membership.ClusterMembership` ledger
(alive → draining → dead) with heartbeat-miss detection on the shared
clock and optional autoscale driven by the backlog/occupancy gauges.
When a decode worker dies (killed, heartbeat-missed, watchdog-stalled)
or drains (preempted via its
:class:`~apex_tpu.resilience.preemption.PreemptionHandler`), its live
requests' pool blocks ship to a surviving worker over the SAME
extract/pack/insert wire a prefill handoff takes — verbatim for
quantized pools — the slot is reinstalled exactly as a handoff
admission would, and the last unacked token is replayed: resumed
streams are **bitwise identical** to an uninterrupted run
(``tests/test_serve_chaos.py`` pins it, greedy and sampled, fp32 and
int8/int4 pools). Every handoff is CRC-stamped; a transfer that rots,
stalls past ``transfer_timeout_ms`` or drops is detected and retried
with exponential backoff — the stream never silently diverges, and a
transfer that exhausts ``transfer_max_retries`` becomes an explicit
``transfer_failed`` terminal state, never a hang.

Parity is the design invariant, not an aspiration: the prefill hosts run
the engine's own chunk program, the wire ships pool blocks bitwise (raw
mode, and quantized pools under EITHER mode), and the decode hosts
install slots exactly as local prefill completion would — so per-request
token streams from a multi-host cluster are **bitwise equal** to the
single-engine path, greedy and sampled
(``tests/test_serve_cluster.py`` pins it). Overload degrades by
shedding and failure degrades by migrating: the cluster never deadlocks
and never raises the engine's pool-exhaustion error.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from apex_tpu.monitor.alerts import AlertEngine, AlertRule, Condition
from apex_tpu.monitor.attrib import AttributionAccumulator
from apex_tpu.monitor.events import EventLog
from apex_tpu.monitor.flight import FlightRecorder
from apex_tpu.monitor.meter import CostModel, Meter
from apex_tpu.monitor.hist import DEFAULT_LATENCY_SPEC, Histogram
from apex_tpu.monitor.registry import FleetScraper, MetricsRegistry
from apex_tpu.monitor.trace import span
from apex_tpu.resilience.preemption import StallWatchdog
from apex_tpu.serve.cluster.chaos import ClusterChaos
from apex_tpu.serve.cluster.membership import (
    ALIVE,
    DEAD,
    DRAINING,
    AutoscalePolicy,
    ClusterMembership,
)
from apex_tpu.serve.cluster.router import Router, RouterConfig, ShedDecision
from apex_tpu.serve.cluster.transfer import (
    SimTransport,
    corrupt_payload,
    pack_blocks,
    payload_crc32,
    validate_wire_mode,
)
from apex_tpu.serve.cluster.workers import (
    DecodeWorker,
    KVHandoff,
    PrefillWorker,
    _cache_size_of,
)
from apex_tpu.serve.engine import Request, ServeConfig

Pytree = Any

__all__ = ["ClusterConfig", "ServeCluster"]


class _WorkerSink:
    """Per-worker step-record shim: stamps ``host=`` on every record so
    step records join the host-attributed event stream, rings it into
    the worker's flight recorder (which forwards to the shared sink)."""

    def __init__(self, ring: FlightRecorder, host: str):
        self._ring = ring
        self._host = host

    def write(self, step=None, metrics=None, **extra) -> None:
        extra.setdefault("host", self._host)
        self._ring.write(step=step, metrics=metrics, **extra)

    def flush(self) -> None:
        self._ring.flush()


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    """Cluster shape. ``serve`` configures each DECODE host's engine
    (slots, pool, kv_quant, spec_k, megakernel…); prefill hosts derive
    their staging config from it. ``wire_mode`` picks the transfer codec
    (``"int8"`` on a float pool cuts wire bytes ~3.6×; quantized pools
    ship their codes+scales verbatim either way). ``link_fixed_ms`` /
    ``link_gib_per_s`` shape the simulated transport's modeled latency
    (both 0: instant — the deterministic test default).

    Elastic knobs (all off by default — a cluster with none of them set
    behaves exactly like the pre-elastic one): ``heartbeat_timeout_ms``
    declares a worker dead after that long without a beat on the shared
    clock; ``watchdog_timeout_ms`` arms one
    :class:`~apex_tpu.resilience.preemption.StallWatchdog` per decode
    worker on the same clock (diagnostics to the sink, then death +
    migration); ``transfer_timeout_ms`` / ``transfer_max_retries`` /
    ``retry_backoff_ms`` govern the CRC/timeout retry ladder on the
    handoff wire; ``autoscale`` turns the backlog/occupancy gauges into
    join/drain decisions."""

    n_prefill: int = 1
    n_decode: int = 1
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)
    router: RouterConfig = dataclasses.field(default_factory=RouterConfig)
    wire_mode: str = "raw"
    prefill_queue_limit: int = 1
    link_fixed_ms: float = 0.0
    link_gib_per_s: float = 0.0
    heartbeat_timeout_ms: Optional[float] = None
    watchdog_timeout_ms: Optional[float] = None
    transfer_timeout_ms: Optional[float] = None
    transfer_max_retries: int = 3
    retry_backoff_ms: float = 10.0
    autoscale: Optional[AutoscalePolicy] = None
    # fleet observability (monitor tier 3). scrape_every: FleetScraper
    # cadence in cluster ticks; extra declarative alert rules ride
    # alert_rules (the autoscale policy's thresholds compile into
    # scale_up/scale_down rules automatically). flight_capacity bounds
    # the per-worker flight-recorder rings; flight_dir (when set) is
    # where rings dump on chaos kill / watchdog fire / page-severity
    # alert escalation (unset: rings still record, dump on demand via
    # ServeCluster.dump_flight).
    scrape_every: int = 1
    alert_rules: Tuple[Any, ...] = ()
    flight_capacity: int = 2048
    flight_dir: Optional[str] = None
    # performance forensics (monitor tier 4). metering: one shared
    # Meter across the decode fleet — every retirement charges its
    # tenant (modeled flops, KV block-seconds, adapter residency), the
    # wire charges at delivery, sheds at the shed funnel; cost_model
    # prices the resources (None: DEFAULT_WEIGHTS); meter_max_tenants
    # bounds the ledger (overflow folds loudly into "_overflow").
    # attribution: an AttributionAccumulator tapped on the shared
    # EventLog decomposes every retired request's e2e into queue/
    # prefill/transfer/decode/stall components on cluster.stats().
    # Both default ON (host-side dict work only; their cost on the
    # chip is not measured); OFF restores the tier-3 cluster.
    metering: bool = True
    attribution: bool = True
    cost_model: Optional[CostModel] = None
    meter_max_tenants: int = 1024

    def validate(self) -> None:
        if self.n_prefill < 1:
            raise ValueError("n_prefill must be >= 1")
        if self.n_decode < 1:
            raise ValueError("n_decode must be >= 1")
        validate_wire_mode(self.wire_mode)
        self.serve.validate()
        self.router.validate()
        if self.link_fixed_ms < 0 or self.link_gib_per_s < 0:
            raise ValueError("link latency knobs must be >= 0")
        for knob in ("heartbeat_timeout_ms", "watchdog_timeout_ms",
                     "transfer_timeout_ms"):
            v = getattr(self, knob)
            if v is not None and v <= 0:
                raise ValueError(f"{knob} must be > 0 when given")
        if self.transfer_max_retries < 0:
            raise ValueError("transfer_max_retries must be >= 0")
        if self.retry_backoff_ms < 0:
            raise ValueError("retry_backoff_ms must be >= 0")
        if self.autoscale is not None:
            self.autoscale.validate()
        if self.scrape_every < 0:
            raise ValueError("scrape_every must be >= 0 (0: scraping off)")
        if ((self.autoscale is not None or self.alert_rules)
                and self.scrape_every == 0):
            # autoscaling and alert rules act on the alert engine, and
            # the alert engine evaluates over scraped views — with
            # scraping off every rule would silently never fire; fail
            # the configuration loudly instead
            raise ValueError(
                "autoscale/alert_rules need scrape_every >= 1: alert "
                "rules evaluate over the scraped fleet view, so a "
                "non-scraping cluster can never fire them")
        if self.flight_capacity < 0:
            raise ValueError(
                "flight_capacity must be >= 0 (0: flight recorder off)")
        if self.meter_max_tenants < 1:
            raise ValueError("meter_max_tenants must be >= 1")


class ServeCluster:
    """Disaggregated serving over simulated (or real) hosts.

    Duck-type compatible with the single :class:`InferenceEngine` where
    it matters — ``submit`` / ``step`` / ``active`` / ``stats`` — so
    ``benchmarks/loadgen.run_workload`` drives a cluster unchanged.
    ``params`` is one replicated pytree (every host serves the same
    model). Streams are retained in :attr:`finished` unless
    ``retain_streams=False`` routes them to ``on_retire``; shed requests
    land in :attr:`shed` (uid → :class:`ShedDecision`) instead — the
    explicit terminal state (reason ``"transfer_failed"`` when the
    retry ladder ran dry).

    ``chaos``: a :class:`~apex_tpu.serve.cluster.chaos.ClusterChaos`
    plan consulted at the top of every tick — the deterministic fault
    harness the elastic claims are proven against."""

    def __init__(self, params: Pytree, cfg, cluster_cfg: ClusterConfig, *,
                 base_key=None, sink=None,
                 events: Optional[EventLog] = None,
                 retain_streams: bool = True,
                 on_retire: Optional[Callable[[str, List[int]], None]] = None,
                 use_pallas: Optional[bool] = None,
                 peak_flops_per_s: Optional[float] = None,
                 chaos: Optional[ClusterChaos] = None):
        cluster_cfg.validate()
        self.cfg = cfg
        self.cluster_cfg = cluster_cfg
        base_key = (base_key if base_key is not None
                    else jax.random.PRNGKey(0))
        # one clock for the whole cluster: every event, latency fold and
        # transfer timestamp subtracts the same anchor
        self._events = events if events is not None else EventLog()
        self._sink = sink
        self.router = Router(cluster_cfg.router)
        self.transport = SimTransport(fixed_ms=cluster_cfg.link_fixed_ms,
                                      gib_per_s=cluster_cfg.link_gib_per_s)
        self.membership = ClusterMembership(
            heartbeat_timeout_ms=cluster_cfg.heartbeat_timeout_ms,
            events=self._events, autoscale=cluster_cfg.autoscale)
        self._chaos = chaos
        # -- fleet observability (monitor tier 3) --------------------------
        # distributed tracing: one trace id minted per submission, bound
        # to the uid so EVERY producer's events carry it
        self._trace_seq = 0
        # flight recorders: one bounded ring per worker + one
        # cluster-scope ring for router/transfer/membership records;
        # records route by their host attribution via an EventLog tap
        self._flight: Dict[str, FlightRecorder] = {}
        self._flight_cluster: Optional[FlightRecorder] = None
        if cluster_cfg.flight_capacity > 0:
            self._flight_cluster = FlightRecorder(
                cluster_cfg.flight_capacity, worker="cluster",
                clock=self._events.now_ms)
            self._events.tap(self._route_flight)
        # alert rules: user rules + the autoscale policy's thresholds
        # compiled into scale_up/scale_down rules — the engine's
        # firings, not raw gauge peeks, are what trigger scaling
        rules = list(cluster_cfg.alert_rules)
        if cluster_cfg.autoscale is not None:
            pol = cluster_cfg.autoscale
            rules.append(AlertRule("scale_up", conditions=(
                Condition("cluster_queue_depth", ">=",
                          pol.scale_up_queue_depth),
                Condition("occupancy", ">=", pol.scale_up_occupancy,
                          agg="avg"))))
            rules.append(AlertRule("scale_down", conditions=(
                Condition("cluster_queue_depth", "<=", 0),
                Condition("occupancy", "<=", pol.scale_down_occupancy,
                          agg="avg"))))
        self._alerts = AlertEngine(rules, events=self._events,
                                   on_fire=self._on_alert)
        self.scraper = FleetScraper(self._scrape_targets,
                                    clock=self._events.now_ms)
        scfg = cluster_cfg.serve
        # decode hosts keep the full engine feature set minus the prefix
        # cache (blocks arrive by wire, not by content address); prefill
        # hosts need no speculation/megakernel — they never decode
        self._decode_cfg = dataclasses.replace(scfg, prefix_cache=False)
        self._prefill_cfg = dataclasses.replace(
            scfg, prefix_cache=False, spec_k=0, megakernel="off")
        self._retain_streams = retain_streams
        self._on_retire = on_retire
        self._finished: Dict[str, List[int]] = {}
        self.shed: Dict[str, ShedDecision] = {}
        # ctor args retained so autoscale can spawn identical workers
        self._params = params
        self._base_key = base_key
        self._use_pallas = use_pallas
        self._peak_flops_per_s = peak_flops_per_s
        # -- performance forensics (monitor tier 4) ------------------------
        # ONE meter shared by every decode host (each charge stamps the
        # retiring worker's name, so per-worker cost rates fall out of
        # the shared pool), created BEFORE the workers that hold it
        self.meter: Optional[Meter] = None
        if cluster_cfg.metering:
            self.meter = Meter(model=cluster_cfg.cost_model,
                               max_tenants=cluster_cfg.meter_max_tenants)
        # latency attribution: a tap on the shared EventLog streams
        # every retirement's lifecycle into the five-component
        # decomposition — no producer knows it exists
        self.attrib: Optional[AttributionAccumulator] = None
        if cluster_cfg.attribution:
            self.attrib = AttributionAccumulator()
            self._events.tap(self.attrib.tap)
        self.prefill_workers = [
            PrefillWorker(params, cfg, self._prefill_cfg, base_key=base_key,
                          wire_mode=cluster_cfg.wire_mode,
                          events=self._events,
                          now_ms=self._events.now_ms,
                          queue_limit=cluster_cfg.prefill_queue_limit,
                          use_pallas=use_pallas, name=f"prefill{i}")
            for i in range(cluster_cfg.n_prefill)]
        for w in self.prefill_workers:
            self._arm_flight(w.name)
        self.decode_workers = [
            self._make_decode_worker(f"decode{i}")
            for i in range(cluster_cfg.n_decode)]
        self._next_decode_id = cluster_cfg.n_decode
        self._workers: Dict[str, Any] = {
            w.name: w for w in self.prefill_workers + self.decode_workers}
        t0 = self._now_ms()
        for w in self.prefill_workers:
            self.membership.join(w.name, "prefill", t0)
        for w in self.decode_workers:
            self.membership.join(w.name, "decode", t0)
        # chaos-stalled workers: name -> step index the stall ends at
        # (None: wedged until something declares it dead)
        self._stalled: Dict[str, Optional[int]] = {}
        # per-decode-worker stall watchdogs on the shared clock (seconds)
        self._watchdogs: Dict[str, StallWatchdog] = {}
        if cluster_cfg.watchdog_timeout_ms is not None:
            for w in self.decode_workers:
                self._arm_watchdog(w.name)
        # the ONE extract program migration uses, shared by every decode
        # worker (identical kv config + padded shape) — a kill-and-
        # migrate on warmed workers mints ZERO new compilations
        decode_kv = self.decode_workers[0].engine.kv_cfg
        wire_mode = cluster_cfg.wire_mode

        def migrate_extract(cache, ids):
            return pack_blocks(cache, decode_kv, ids, wire_mode=wire_mode)

        self._migrate_extract = jax.jit(migrate_extract)
        # transfer reliability: uid -> {handoff, attempt, deadline};
        # resends scheduled on the shared clock with exponential backoff
        self._awaiting: Dict[str, Dict[str, Any]] = {}
        self._resend_at: List[Tuple[float, int, str]] = []  # (t, seq, uid)
        self._resend_seq = 0
        self._redeliver: List[KVHandoff] = []  # delivered, unplaced
        self.migrations_total = 0
        self.transfer_retries = 0
        self.transfer_crc_failures = 0
        self.transfer_timeouts = 0
        self.transfer_failed = 0
        self.duplicates_ignored = 0
        # per-tenant LoRA: the cluster-level adapter CATALOG (name ->
        # (weights, scale)). Loading puts the adapter eagerly into every
        # prefill host (prompts place by feasibility, not warmth) and
        # lazily into decode hosts on first cold placement — the
        # router's warm preference keeps cold loads rare at steady state
        self._adapter_catalog: Dict[str, Tuple[Any, float]] = {}
        self.adapter_loads = 0        # cold decode-side catalog loads
        # hard capacity for the unservable check: the roomiest decode pool
        self._max_servable_tokens = max(
            w.engine.kv_cfg.num_blocks * w.engine.kv_cfg.block_size
            for w in self.decode_workers)
        self.max_context = self.decode_workers[0].engine.max_context
        self.transfer_ms_hist = Histogram(DEFAULT_LATENCY_SPEC)
        self._step_idx = 0
        self._t_first_submit_ms: Optional[float] = None
        # start time of the PREVIOUS tick: the heartbeat/watchdog floor
        # (a worker that beat during that tick took its chance — one
        # slow wall-clock tick must not age the whole fleet to death)
        self._prev_tick_start_ms: Optional[float] = None

    def _make_decode_worker(self, name: str) -> DecodeWorker:
        ring = self._arm_flight(name)
        # the engine's step records flow host-stamped through the
        # worker's flight ring (which forwards to the shared sink) —
        # the ring is the black box, the sink stays the durable log
        sink = (_WorkerSink(ring, name) if ring is not None
                else self._sink)
        return DecodeWorker(
            self._params, self.cfg, self._decode_cfg,
            base_key=self._base_key,
            wire_mode=self.cluster_cfg.wire_mode, sink=sink,
            events=self._events, slo=self.cluster_cfg.router.slo,
            retain_streams=False, on_retire=self._retired,
            use_pallas=self._use_pallas,
            peak_flops_per_s=self._peak_flops_per_s,
            meter=self.meter, name=name)

    # -- flight recorders (monitor tier 3) ---------------------------------
    def _arm_flight(self, name: str) -> Optional[FlightRecorder]:
        if self.cluster_cfg.flight_capacity <= 0:
            return None
        ring = self._flight.get(name)
        if ring is None:
            ring = FlightRecorder(
                self.cluster_cfg.flight_capacity, worker=name,
                inner=self._sink, clock=self._events.now_ms)
            self._flight[name] = ring
        return ring

    def _route_flight(self, rec: Dict[str, Any]) -> None:
        """EventLog tap: every event/gauge record lands in exactly one
        ring — the named worker's when the record is host-attributed
        (bound or explicit), else the cluster-scope ring."""
        host = rec.get("host") or rec.get("worker")
        ring = self._flight.get(host) if host is not None else None
        if ring is not None:
            ring.record(rec)
        elif self._flight_cluster is not None:
            self._flight_cluster.record(rec)

    def _flight_rings(self) -> Dict[str, FlightRecorder]:
        out = dict(self._flight)
        if self._flight_cluster is not None:
            out["cluster"] = self._flight_cluster
        return out

    def dump_flight(self, directory: Optional[str] = None,
                    reason: str = "manual",
                    workers: Optional[Sequence[str]] = None) -> List[str]:
        """Atomically dump flight rings (all, or ``workers``) into
        ``directory`` (default ``ClusterConfig.flight_dir``); returns
        the dump paths and events each dump. ``python -m
        apex_tpu.monitor.postmortem DIR`` rebuilds the merged timeline
        from these files alone. With NO directory configured but a
        durable sink wired, each ring instead streams into the shared
        JSONL as one contiguous ``write_many`` batch (header-fenced) —
        the black box lands in the log the operator already has."""
        directory = directory or self.cluster_cfg.flight_dir
        if self.cluster_cfg.flight_capacity <= 0:
            return []
        t = self._now_ms()
        paths = []
        to_sink = (directory is None and self._sink is not None
                   and hasattr(self._sink, "write_many"))
        if directory is None and not to_sink:
            return []
        for name, ring in sorted(self._flight_rings().items()):
            if workers is not None and name not in workers:
                continue
            if to_sink:
                ring.dump_to_sink(self._sink, reason=reason, t_ms=t)
                path = f"sink:{name}"
            else:
                path = ring.dump(directory, reason=reason, t_ms=t)
            paths.append(path)
            self._events.emit("flight_dump", t_ms=t, worker=name,
                              reason=reason, path=path)
        return paths

    def _flight_sink_ok(self) -> bool:
        return (self.cluster_cfg.flight_dir is not None
                or (self._sink is not None
                    and hasattr(self._sink, "write_many")))

    def _dump_on_death(self, name: str, reason: str) -> None:
        """A worker died for a non-voluntary reason: preserve ITS ring
        and the cluster-scope ring (router/transfer context) before the
        telemetry goes stale — the chaos-kill black-box path."""
        if self._flight_sink_ok():
            self.dump_flight(reason=reason,
                             workers=(name, "cluster"))

    def _on_alert(self, firing) -> None:
        """Page-severity firings escalate: every surviving ring dumps
        (the 'capture the whole fleet's last seconds' trigger)."""
        if firing.severity == "page" and self._flight_sink_ok():
            self.dump_flight(reason=f"alert:{firing.rule}")

    def _arm_watchdog(self, name: str) -> None:
        self._watchdogs[name] = StallWatchdog(
            timeout_s=self.cluster_cfg.watchdog_timeout_ms / 1e3,
            sink=self._sink,
            clock=lambda: self._events.now_ms() / 1e3)

    # -- fleet scraping (monitor tier 3) -----------------------------------
    def _scrape_targets(self) -> List:
        """The FleetScraper's live target set: the cluster's own series
        plus every non-dead worker. A chaos-stalled worker is a SCRAPE
        MISS (its target answers None) — coverage drops below 1.0 and
        an absence rule over its series can fire, exactly how a wedged
        exporter looks to a real scraper."""
        out: List = [("cluster", self._scrape_self)]
        for w in self.prefill_workers + self.decode_workers:
            if self._state(w.name) == DEAD:
                continue
            if w.name in self._stalled:
                out.append((w.name, lambda: None))
            else:
                out.append((w.name, w.scrape))
        return out

    def _scrape_self(self) -> Dict[str, Any]:
        """Router/transport/membership series (the per-tenant plane
        rides tenant labels; the registry bound tracks the router's own
        tenant-state bound so a tenant flood degrades loudly, never
        unboundedly)."""
        limit = self.cluster_cfg.router.max_tenant_states or 1024
        # headroom: 3 router series + 3 meter series per tenant, plus
        # the fixed cluster series — both tenant planes are themselves
        # cardinality-bounded (router GC, meter overflow fold)
        reg = MetricsRegistry(max_series=8 * limit + 64)
        t = self._now_ms()
        L = {"worker": "cluster"}
        r = self.router
        reg.gauge("cluster_queue_depth", float(r.queue_depth), t_ms=t, **L)
        reg.gauge("queued_tokens", float(r.queued_tokens()), t_ms=t, **L)
        reg.counter("submitted_total", r.submitted, **L)
        reg.counter("admitted_total", r.admitted, **L)
        reg.counter("shed_total", r.shed, **L)
        reg.gauge("shed_rate",
                  (r.shed / r.submitted) if r.submitted else 0.0,
                  t_ms=t, **L)
        reg.gauge("transfers_in_flight", float(self.transport.in_flight),
                  t_ms=t, **L)
        reg.counter("transfer_retries_total", self.transfer_retries, **L)
        reg.counter("migrations_total", self.migrations_total, **L)
        if self.cluster_cfg.serve.lora_rank > 0:
            reg.counter("adapter_warm_dispatches_total",
                        r.adapter_warm_dispatches, **L)
            reg.counter("adapter_cold_dispatches_total",
                        r.adapter_cold_dispatches, **L)
            reg.counter("adapter_catalog_loads_total",
                        self.adapter_loads, **L)
        reg.counter("worker_deaths_total", self.membership.worker_deaths,
                    **L)
        for tenant, rec in self.router.tenants.items():
            reg.counter("tenant_submitted_total", rec["submitted"],
                        tenant=tenant)
            reg.counter("tenant_admitted_total", rec["admitted"],
                        tenant=tenant)
            reg.counter("tenant_shed_total", rec["shed"], tenant=tenant)
        if self.membership.heartbeat_timeout_ms is not None:
            for name in self.membership.names():
                wrec = self.membership.record(name)
                if wrec.state != DEAD:
                    reg.gauge("heartbeat_age_ms",
                              max(0.0, t - wrec.last_beat_ms),
                              t_ms=t, worker=name)
        if self.meter is not None:
            self.meter.collect_registry(reg, t_ms=t)
        return reg.snapshot(t)

    # -- adapter catalog (per-tenant LoRA) ---------------------------------
    def load_adapter(self, name: str, weights: Any, *,
                     scale: float = 1.0) -> None:
        """Register a named LoRA adapter fleet-wide. Eager into every
        prefill host NOW (the prompt's K/V must be written with adapted
        projections wherever it lands); decode hosts pick it up lazily —
        the router prefers adapter-warm workers, and a cold placement
        triggers the worker-local ``adapter_load`` there. Requires
        ``ServeConfig(lora_rank > 0)``."""
        if self.cluster_cfg.serve.lora_rank <= 0:
            raise RuntimeError(
                "adapters are disabled (ServeConfig.lora_rank == 0) — "
                "configure lora_rank/max_adapters to serve adapters")
        self._adapter_catalog[name] = (weights, float(scale))
        for w in self.prefill_workers:
            if self._state(w.name) != DEAD and w.adapters is not None:
                if w.adapters.lookup(name) is None:
                    w.load_adapter(name, weights, scale=scale)
                    self._events.emit("adapter_load", name,
                                      worker=w.name, eager=True)

    def adapter_catalog(self) -> List[str]:
        return sorted(self._adapter_catalog)

    def _ensure_adapter_on(self, worker: DecodeWorker,
                           name: str, t_ms: float) -> bool:
        """Make ``name`` resident on ``worker`` before a handoff bound
        to it is admitted (restore raises on a cold registry). False
        when the worker's pool is wholly pinned by decoding slots —
        the caller defers placement, never crashes."""
        eng = worker.engine
        if eng.adapters is not None and eng.adapters.lookup(name) is not None:
            return True
        weights, scale = self._adapter_catalog[name]
        try:
            worker.load_adapter(name, weights, scale=scale)
        except RuntimeError:
            return False
        self.adapter_loads += 1
        # the load IS liveness — advertise immediately so handoffs later
        # this same tick see the fresh resident set, not last tick's
        self.membership.beat(worker.name, t_ms,
                             adapters=worker.resident_adapters())
        return True

    # -- lifecycle ---------------------------------------------------------
    def _now_ms(self) -> float:
        return self._events.now_ms()

    def _retired(self, uid: str, tokens: List[int]) -> None:
        if self._retain_streams:
            self._finished[uid] = tokens
        if self._on_retire is not None:
            self._on_retire(uid, tokens)
        # terminal: the trace's bound fields (trace id, tenant, host)
        # are no longer needed — the table stays O(in-flight)
        self._events.unbind(uid)

    def submit(self, request: Request) -> None:
        """Route one request in. Input validation mirrors the engine's
        (garbage raises); a request that can never FIT the decode pool is
        shed — terminal, recorded, never a deadlock."""
        p = len(request.tokens)
        if p < 1:
            raise ValueError(f"{request.uid}: empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError(f"{request.uid}: max_new_tokens must be >= 1")
        if p >= self.max_context:
            raise ValueError(
                f"{request.uid}: prompt ({p}) must leave room to generate "
                f"(max_context {self.max_context})")
        t = self._now_ms()
        if self._t_first_submit_ms is None:
            self._t_first_submit_ms = t
        # mint the request's trace id HERE — router submission is the
        # start of the distributed trace; binding threads it (plus the
        # tenant) through every later producer's events, across hosts
        # and migrations, without any producer knowing about tracing
        self._trace_seq += 1
        self._events.bind(request.uid,
                          trace=f"tr{self._trace_seq:06d}",
                          tenant=getattr(request, "tenant", "default"))
        self._events.emit("submitted", request.uid, t_ms=t,
                          prompt_tokens=p,
                          max_new_tokens=request.max_new_tokens,
                          tenant=getattr(request, "tenant", "default"))
        adapter = getattr(request, "adapter", None)
        if adapter is not None and adapter not in self._adapter_catalog:
            # bound to an adapter nobody registered: terminal shed at
            # the front door — NEVER served on the base model by
            # accident, never a crash deep in a worker
            self._record_shed(self.router.shed_submitted(
                request, "unknown_adapter", t))
            self._events.gauge("queue_depth", self.router.queue_depth,
                               t_ms=t)
            return
        total = min(p + request.max_new_tokens, self.max_context)
        decision = self.router.submit(
            request, t, total_tokens=total,
            max_servable_tokens=self._max_servable_tokens)
        if decision is not None:
            self._record_shed(decision)
        self._events.gauge("queue_depth", self.router.queue_depth, t_ms=t)

    def _record_shed(self, d: ShedDecision) -> None:
        self.shed[d.request.uid] = d
        if self.meter is not None:
            # the single shed-charge funnel: EVERY terminal shed (front
            # door, infeasible dispatch, transfer_failed, headless)
            # flows through here exactly once — the engine deliberately
            # never charges sheds, so there is no double-count
            self.meter.charge(getattr(d.request, "tenant", "default"),
                              t_ms=d.t_ms, shed=1)
        self._events.emit(
            "shed", d.request.uid, t_ms=d.t_ms, reason=d.reason,
            predicted_ttft_ms=(round(d.predicted_ttft_ms, 3)
                               if d.predicted_ttft_ms is not None else None),
            budget_ms=d.budget_ms)
        self._events.unbind(d.request.uid)  # terminal state

    # -- membership views --------------------------------------------------
    def _state(self, name: str) -> str:
        return self.membership.state(name)

    def _steppable(self, name: str) -> bool:
        return self._state(name) != DEAD and name not in self._stalled

    def alive_decode_workers(self) -> List[DecodeWorker]:
        return [w for w in self.decode_workers if self._state(w.name) == ALIVE]

    def alive_prefill_workers(self) -> List[PrefillWorker]:
        return [w for w in self.prefill_workers
                if self._state(w.name) == ALIVE]

    # -- elastic transitions (chaos entry points + real operations) --------
    def kill_worker(self, name: str) -> None:
        """Fail-stop ``name`` NOW: out of the dispatch set, decode slots
        migrate to survivors, staged prefill prompts re-enqueue at the
        router. (The simulated failure keeps the dying pool readable —
        the preemption-notice / reachable-HBM failure class the KV wire
        can actually rescue; a hard asic loss would re-prefill instead,
        which the prefill re-enqueue path already covers.)"""
        t = self._now_ms()
        if not self.membership.mark_dead(name, t, "killed"):
            return
        self._evacuate(name, t)
        # black box: the dying worker's ring (holding its last records
        # INCLUDING the migrate_start exits evacuation just stamped) and
        # the cluster ring's router-side context dump atomically — the
        # postmortem CLI rebuilds the pre-kill timeline from these alone
        self._dump_on_death(name, "killed")

    def preempt_worker(self, name: str) -> None:
        """Deliver a preemption through the worker's PreemptionHandler —
        the exact path a real SIGTERM takes. The drain protocol runs on
        the next tick."""
        self._workers[name].preemption.trigger()

    def stall_worker(self, name: str, for_steps: int = 0) -> None:
        """Chaos: ``name`` stops stepping (and beating) for
        ``for_steps`` ticks (0: until declared dead)."""
        self._stalled[name] = (self._step_idx + for_steps
                               if for_steps > 0 else None)

    def request_drain(self, name: str, reason: str = "drained") -> None:
        """Voluntary exit: decode migrates its live requests now and
        leaves; prefill finishes its current prompt, re-enqueues the
        rest, and leaves when idle."""
        t = self._now_ms()
        if not self.membership.mark_draining(name, t, reason):
            return
        w = self._workers[name]
        if isinstance(w, DecodeWorker):
            self._evacuate(name, t)
            self.membership.mark_dead(name, t, reason)
        else:
            for req, t_sub in reversed(w.drain_queued()):
                self.router.requeue(req, t_sub)
            if not w.busy:
                self.membership.mark_dead(name, t, reason)

    def _evacuate(self, name: str, t_ms: float) -> None:
        """Move everything off a dead/draining worker: pending handoffs
        re-dispatch, live decode slots migrate over the KV wire, staged
        prefill prompts re-enqueue at the router."""
        w = self._workers[name]
        if isinstance(w, PrefillWorker):
            aborted = w.abort_current()
            if aborted is not None:
                self.router.requeue(*aborted)
            for req, t_sub in reversed(w.drain_queued()):
                self.router.requeue(req, t_sub)
            return
        for h in w.drain_pending():
            # not yet installed: just re-place on a survivor (the
            # payload is cluster-side and its transfer already counted
            # — no new wire transit, no new transfer telemetry)
            self._redeliver.append(h)
        for uid in w.live_uids():
            self._events.emit("migrate_start", uid, t_ms=t_ms,
                              src=name)
            h = w.evict_to_handoff(uid, self._migrate_extract)
            self.migrations_total += 1
            self._send_handoff(h, t_ms)

    # -- transfer reliability ----------------------------------------------
    def _send_handoff(self, h: KVHandoff, t_ms: float,
                      attempt: int = 1) -> None:
        uid = h.request.uid
        timeout = self.cluster_cfg.transfer_timeout_ms
        self._awaiting[uid] = {
            "handoff": h, "attempt": attempt,
            "deadline": (t_ms + timeout) if timeout is not None else None,
        }
        with span("transfer"):
            self._events.emit("transfer_start", uid, t_ms=t_ms,
                              wire_bytes=h.wire_bytes,
                              n_blocks=h.n_blocks, handoff_kind=h.kind,
                              attempt=attempt)
            self.transport.send((h, attempt), h.wire_bytes, t_ms)

    def _schedule_retry(self, uid: str, t_ms: float, reason: str) -> None:
        entry = self._awaiting.get(uid)
        if entry is None:
            return
        if entry["attempt"] > self.cluster_cfg.transfer_max_retries:
            # retry ladder ran dry: explicit terminal state, never a
            # hang — and the router's ledger moves it admitted → shed
            # so shed_rate reflects the loss
            del self._awaiting[uid]
            self.transfer_failed += 1
            h = entry["handoff"]
            self._record_shed(self.router.shed_admitted(
                h.request, "transfer_failed", t_ms))
            return
        self.transfer_retries += 1
        self._events.emit("transfer_retry", uid, t_ms=t_ms, reason=reason,
                          attempt=entry["attempt"])
        backoff = (self.cluster_cfg.retry_backoff_ms
                   * (2 ** (entry["attempt"] - 1)))
        entry["attempt"] += 1
        entry["deadline"] = None  # re-armed when the resend goes out
        self._resend_seq += 1
        heapq.heappush(self._resend_at,
                       (t_ms + backoff, self._resend_seq, uid))

    def _pump_retries(self, t_ms: float) -> int:
        """Resend due retries; time out overdue transfers."""
        n = 0
        while self._resend_at and self._resend_at[0][0] <= t_ms:
            _, _, uid = heapq.heappop(self._resend_at)
            entry = self._awaiting.get(uid)
            if entry is None:
                continue
            self._send_handoff(entry["handoff"], t_ms,
                               attempt=entry["attempt"])
            n += 1
        for uid, entry in list(self._awaiting.items()):
            if entry["deadline"] is not None and t_ms >= entry["deadline"]:
                self.transfer_timeouts += 1
                self._schedule_retry(uid, t_ms, "timeout")
                n += 1
        return n

    def _deliver(self, t_ms: float) -> int:
        n = 0
        for d in self.transport.poll(t_ms):
            h, attempt = d.item
            uid = h.request.uid
            entry = self._awaiting.get(uid)
            if entry is None:
                # already satisfied by an earlier copy: true duplicate
                self.duplicates_ignored += 1
                continue
            payload = (corrupt_payload(h.payload) if d.corrupted
                       else h.payload)
            valid = (h.crc32 is None
                     or payload_crc32(payload) == h.crc32)
            if attempt != entry["attempt"]:
                # a copy from a superseded attempt (it stalled past the
                # timeout and a retry is pending): a VALID copy still
                # satisfies the request — first good copy wins, and the
                # scheduled resend lapses against the empty awaiting
                # entry, saving the backoff wait and a full KV
                # retransmit. An invalid one is just dropped: the newer
                # attempt is already underway.
                if not valid:
                    self.duplicates_ignored += 1
                    continue
            elif not valid:
                self.transfer_crc_failures += 1
                self._schedule_retry(uid, t_ms, "crc")
                continue
            # validated: the transfer is DONE exactly once (one
            # transfer_end, one histogram sample) whether or not a
            # destination is alive right now — placement is a separate
            # concern handled below
            del self._awaiting[uid]
            self.transfer_ms_hist.add([d.transfer_ms])
            self._events.emit(
                "transfer_end", uid, t_ms=d.t_deliver_ms,
                wire_bytes=d.wire_bytes, handoff_kind=h.kind,
                transfer_ms=round(d.transfer_ms, 3))
            if self.meter is not None:
                # the wire is fleet infrastructure, not a worker — the
                # charge carries no worker attribution, and a retried
                # transfer bills each transit (retries cost real bytes)
                self.meter.charge(
                    getattr(h.request, "tenant", "default"),
                    t_ms=d.t_deliver_ms, wire_bytes=d.wire_bytes)
            self._redeliver.append(h)
            n += 1
        # place everything delivered-but-unplaced (fresh arrivals above,
        # plus handoffs evacuated from a dead worker's pending queue —
        # those crossed the wire once already and get NO new transfer
        # telemetry). Placement is the router's adapter-aware pick over
        # the membership advertisements: least-loaded among the
        # ADAPTER-WARM workers when the handoff is adapter-bound, else
        # classic least-loaded; a cold pick loads the adapter from the
        # catalog first (the explicit adapter_load lifecycle event).
        if self._redeliver and self.alive_decode_workers():
            todo, self._redeliver = self._redeliver, []
            for h in todo:
                alive = self.alive_decode_workers()
                cands = [(w.name, w.load,
                          self.membership.record(w.name).adapters)
                         for w in alive]
                name = self.router.select_worker(cands, adapter=h.adapter)
                if h.adapter is None:
                    self._workers[name].admit(h)
                    continue
                # adapter-bound: the adapter must be RESIDENT before the
                # restore lands. Try the router's pick first, then the
                # rest by load; a fleet whose every pool is pinned
                # defers to the next tick (never a crash, never a hang
                # — retiring slots free pool capacity)
                ordered = [name] + [
                    c[0] for c in sorted(cands, key=lambda c: c[1])
                    if c[0] != name]
                for wname in ordered:
                    w2 = self._workers[wname]
                    if self._ensure_adapter_on(w2, h.adapter, t_ms):
                        w2.admit(h)
                        break
                else:
                    self._redeliver.append(h)
        return n

    def _abort_if_headless(self, t_ms: float) -> int:
        """No ALIVE decode worker and no autoscale to mint one: every
        delivered-or-in-flight handoff (and everything still queued at
        the router) can never be served — turn them into explicit
        ``no_decode_workers`` terminal sheds instead of waiting forever.
        With autoscale armed the cluster instead waits for the join."""
        if self.alive_decode_workers() or (
                self.membership.autoscale_policy is not None):
            return 0
        n = 0
        doomed: List[Request] = [h.request for h in self._redeliver]
        self._redeliver.clear()
        for entry in self._awaiting.values():
            doomed.append(entry["handoff"].request)
        self._awaiting.clear()
        self._resend_at.clear()
        # in-flight requests were admitted: the router moves them to its
        # shed column; queued ones shed through the normal queue path —
        # either way the per-tenant ledger stays exact
        for req in doomed:
            self._record_shed(self.router.shed_admitted(
                req, "no_decode_workers", t_ms))
            n += 1
        for d in self.router.shed_queued("no_decode_workers", t_ms):
            self._record_shed(d)
            n += 1
        return n

    # -- failure detection (per tick) --------------------------------------
    def _poll_preemptions(self, t_ms: float) -> int:
        n = 0
        for name, w in list(self._workers.items()):
            if (self._state(name) == ALIVE and w.preemption.preempted()):
                self.request_drain(name, "preempted")
                n += 1
        return n

    def _finish_drains(self, t_ms: float) -> None:
        # draining prefill workers leave once their current prompt ships
        for w in self.prefill_workers:
            if self._state(w.name) == DRAINING and not w.busy:
                self.membership.mark_dead(
                    w.name, t_ms,
                    self.membership.record(w.name).reason or "drained")

    def _check_watchdogs(self, t_ms: float,
                         beat_floor_ms: Optional[float] = None) -> int:
        n = 0
        for name, wd in self._watchdogs.items():
            if self._state(name) == DEAD:
                continue
            if (beat_floor_ms is not None
                    and self.membership.record(name).last_beat_ms
                    >= beat_floor_ms):
                continue  # beat during the previous tick: not wedged
            if wd.check(now=t_ms / 1e3):
                w = self._workers[name]
                if self._sink is not None:
                    self._sink.write(
                        step=self._step_idx, phase="watchdog",
                        worker=name,
                        occupied_slots=len(w.live_uids()),
                        handoffs_pending=len(w._pending),
                        last_beat_ms=round(
                            self.membership.record(name).last_beat_ms, 3))
                # the watchdog verdict is an alert: same ledger, same
                # events, same escalation plane as an evaluated rule
                self._alerts.fire("watchdog_stall", t_ms, worker=name)
                self.membership.mark_dead(name, t_ms, "stall")
                self._evacuate(name, t_ms)
                self._dump_on_death(name, "stall")
                n += 1
        return n

    def _autoscale(self, t_ms: float) -> None:
        """Act on the ALERT ENGINE's scale firings (the thresholds are
        declarative rules over the scraped fleet view — no gauge
        peeking here); membership's ``approve_scale`` stays the one
        cooldown/fleet-bounds actuation gate."""
        if self.membership.autoscale_policy is None:
            return
        if not self.alive_decode_workers():
            # headless with autoscale armed: no occupancy series exists
            # for a rule to fire on (zero capacity exports nothing), but
            # lost capacity must be replaced or the fleet stays headless
            # forever — an explicit page-severity firing records WHY the
            # spawn happened, then spawn immediately (0 alive is always
            # under the fleet cap, which counts ALIVE workers)
            self._alerts.fire("fleet_headless", t_ms, severity="page",
                              alive_decode=0)
            self.spawn_decode_worker()
            self.membership.autoscale_ups += 1
            return
        if (self._alerts.active("scale_up")
                and self.membership.approve_scale("up", t_ms)):
            self.spawn_decode_worker()
        elif self._alerts.active("scale_down"):
            candidates = self.alive_decode_workers()
            if (len(candidates) > 1
                    and self.membership.approve_scale("down", t_ms)):
                victim = min(candidates, key=lambda w: w.load)
                self.request_drain(victim.name, "scale_down")

    def spawn_decode_worker(self) -> DecodeWorker:
        """Join a fresh decode worker at runtime (the autoscale-up hook;
        also callable directly to replace lost capacity). Its programs
        compile on first use — an explicit, bounded cost the compile
        gates exclude by construction (new worker = new program set)."""
        name = f"decode{self._next_decode_id}"
        self._next_decode_id += 1
        w = self._make_decode_worker(name)
        self.decode_workers.append(w)
        self._workers[name] = w
        self.membership.join(name, "decode", self._now_ms())
        if self.cluster_cfg.watchdog_timeout_ms is not None:
            self._arm_watchdog(name)
        return w

    # -- the cluster tick --------------------------------------------------
    def _outstanding(self) -> int:
        """Requests in flight anywhere downstream of the router: mid- or
        awaiting prefill, on the wire (or awaiting a retry), pending or
        occupying a decode slot on a non-dead worker."""
        n = len(self._awaiting) + len(self._redeliver)
        for w in self.prefill_workers:
            if self._state(w.name) == DEAD:
                continue
            n += (1 if w._current is not None else 0) + len(w._queue)
        for w in self.decode_workers:
            if self._state(w.name) == DEAD:
                continue
            n += len(w._pending)
            n += sum(s is not None for s in w.engine._slots)
        return n

    def _pipeline_tokens(self) -> int:
        """Token-denominated outstanding work the feasibility predictor
        charges at the measured prefill rate: unprefilled prompt tokens
        plus the decode side's remaining generation budgets — a
        deliberately simple stand-in for per-stage service curves, but
        one that GROWS with congestion, which is all admission control
        needs."""
        n = sum(w.backlog_tokens for w in self.prefill_workers
                if self._state(w.name) != DEAD)
        for w in self.decode_workers:
            if self._state(w.name) == DEAD:
                continue
            for h in w._pending:
                n += h.request.max_new_tokens
            for s in w.engine._slots:
                if s is not None:
                    n += max(0, s.request.max_new_tokens
                             - len(s.generated))
        return n

    def _dispatch(self, t_ms: float) -> int:
        """Admit from the router while the pipeline has credit. The
        credit bound (ALIVE decode slots + one buffered handoff per
        alive decode host) is BACKPRESSURE: when decode saturates,
        dispatch stops, queue wait mounts at the ROUTER, and the TTFT
        feasibility check — waited + pipeline-work · measured ms/token —
        sheds there, where a rejection is still cheap. Without it,
        prefill would race ahead and mint first tokens whose streams
        then stall for seconds in a decode queue no budget knows
        about. Only ALIVE workers are in the dispatch set — the elastic
        invariant."""
        n = 0
        alive_decode = self.alive_decode_workers()
        if not alive_decode:
            return 0
        capacity = (sum(w.engine.serve_cfg.num_slots for w in alive_decode)
                    + len(alive_decode))
        outstanding = self._outstanding()
        backlog = self._pipeline_tokens()
        for worker in sorted(self.alive_prefill_workers(),
                             key=lambda w: w.backlog_tokens):
            while worker.can_accept and outstanding < capacity:
                item, sheds = self.router.next_request(backlog, t_ms)
                for d in sheds:
                    self._record_shed(d)
                if item is None:
                    return n
                request, t_submit = item
                worker.accept(request, t_submit)
                backlog += len(request.tokens) + request.max_new_tokens
                outstanding += 1
                n += 1
        return n

    def step(self) -> bool:
        """One cluster tick; False when nothing moved anywhere."""
        t = self._now_ms()
        faults = (self._chaos.apply(self, self._step_idx)
                  if self._chaos is not None else [])
        # expire finished chaos stalls (a dead worker's stall is moot —
        # leaving it would make the waiting term below report progress
        # forever after the death was already handled)
        for name, until in list(self._stalled.items()):
            if ((until is not None and self._step_idx >= until)
                    or self._state(name) == DEAD):
                del self._stalled[name]
        moved = len(faults)
        moved += self._poll_preemptions(t)
        floor = self._prev_tick_start_ms
        for name in self.membership.check_heartbeats(t,
                                                     beat_floor_ms=floor):
            # the heartbeat verdict (reached by the beat-floor detector,
            # not a scraped rule — the floor logic needs per-tick state
            # a series can't carry) lands in the alert plane: one
            # ledger, one event stream, and the firing is what precedes
            # the migration in the trace
            self._alerts.fire(
                "heartbeat_absent", t, worker=name,
                last_beat_ms=round(
                    self.membership.record(name).last_beat_ms, 3))
            self._evacuate(name, t)
            self._dump_on_death(name, "heartbeat")
            moved += 1
        moved += self._check_watchdogs(t, floor)
        with span("transfer"):
            delivered = self._deliver(t)
            retried = self._pump_retries(t)
        moved += self._abort_if_headless(t)
        dispatched = self._dispatch(t)
        chunks = 0
        sent = 0
        for w in self.prefill_workers:
            if not self._steppable(w.name):
                continue
            before = w.chunks_run
            h = w.step()
            # beat with a FRESH timestamp: the step above may have been
            # the slow thing (a compile, a long chunk) — the worker that
            # just proved liveness must never look stale for it. The
            # beat carries the worker's ADVERTISEMENT: resident adapter
            # set + quant mode (the heterogeneous-fleet gossip)
            self.membership.beat(
                w.name, self._now_ms(),
                adapters=(sorted(w.adapters.resident())
                          if w.adapters is not None else None),
                quant=w.serve_cfg.kv_quant)
            if w.chunks_run > before:  # feed only a FRESH measurement
                self.router.observe_chunk(w.last_chunk_tokens,
                                          w.last_chunk_ms)
            if w.busy or h is not None:
                chunks += 1
            if h is not None:
                self._send_handoff(h, self._now_ms())
                sent += 1
        self._finish_drains(t)
        decoded = 0
        for w in self.decode_workers:
            if self._state(w.name) != ALIVE or w.name in self._stalled:
                continue
            if w.step():
                decoded += 1
            t_beat = self._now_ms()
            self.membership.beat(
                w.name, t_beat,
                adapters=(w.resident_adapters()
                          if w.engine.adapters is not None else None),
                quant=w.engine.serve_cfg.kv_quant,
                # the tier-4 half of the advertisement: this worker's
                # accrued cost units/second (the ROADMAP 5c routing
                # signal — a fleet-mix policy reads membership, not
                # the meter)
                cost_rate=(self.meter.worker_cost_rate(w.name, t_beat)
                           if self.meter is not None else None))
            wd = self._watchdogs.get(w.name)
            if wd is not None:
                wd.tick(self._step_idx)
        # fleet observability tick: scrape the live workers into one
        # view, evaluate the alert rules over it — autoscale (below)
        # acts on the engine's ACTIVE alerts, not on raw gauges
        if (self.cluster_cfg.scrape_every
                and self._step_idx % self.cluster_cfg.scrape_every == 0):
            with span("scrape"):
                view = self.scraper.scrape(self._now_ms())
            self._alerts.evaluate(view, self._now_ms())
        self._autoscale(t)
        # transfers still on the (modeled-latency) wire — or waiting out
        # a retry backoff / failure-detection timeout — count as pending
        # progress: a driver polling "did anything move?" must not
        # declare the cluster drained while recovery is in flight
        detection_armed = (
            self.cluster_cfg.heartbeat_timeout_ms is not None
            or self.cluster_cfg.watchdog_timeout_ms is not None)
        waiting = (self.transport.in_flight or self._awaiting
                   or self._resend_at or self._redeliver
                   or (bool(self._stalled) and detection_armed))
        progressed = bool(moved or delivered or retried or dispatched
                          or chunks or sent or decoded or waiting)
        self._prev_tick_start_ms = t
        self._step_idx += 1
        if self._sink is not None and progressed:
            self._sink.write(
                step=self._step_idx, phase="cluster",
                queue_depth=self.router.queue_depth,
                prefill_backlog_tokens=sum(
                    w.backlog_tokens for w in self.prefill_workers
                    if self._state(w.name) != DEAD),
                transfers_in_flight=self.transport.in_flight,
                shed_total=self.router.shed)
        return progressed

    # -- driving -----------------------------------------------------------
    @property
    def active(self) -> bool:
        return (self.router.queue_depth > 0
                or any(w.busy for w in self.prefill_workers
                       if self._state(w.name) != DEAD)
                or self.transport.in_flight > 0
                or bool(self._awaiting) or bool(self._redeliver)
                or bool(self._resend_at)
                or any(w.active for w in self.decode_workers
                       if self._state(w.name) != DEAD))

    def run(self, requests: Sequence[Request],
            max_steps: Optional[int] = None) -> Dict[str, List[int]]:
        """Serve ``requests`` to completion (or shed — check
        :attr:`shed`); returns uid → generated tokens for the completed
        ones. Never deadlocks: a tick that moves nothing while work
        remains is impossible by construction (queued work either
        dispatches, sheds, chunks, ships, decodes, migrates or retries),
        and ``max_steps`` is a belt-and-braces bound for drivers."""
        for r in requests:
            self.submit(r)
        steps = 0
        while self.active:
            if max_steps is not None and steps >= max_steps:
                break
            self.step()
            steps += 1
        return dict(self._finished)

    @property
    def finished(self) -> Dict[str, List[int]]:
        return dict(self._finished)

    @property
    def completed(self) -> int:
        return sum(w.engine.completed for w in self.decode_workers)

    def compile_counts(self) -> Dict[str, Any]:
        return {
            "prefill": [w.compile_counts() for w in self.prefill_workers],
            "decode": [w.compile_counts() for w in self.decode_workers],
            "migrate_extract": _cache_size_of(self._migrate_extract),
        }

    def programs(self) -> Dict[str, Callable]:
        """Every jitted program in the cluster, uniquely named — hand
        straight to ``analyze.recompile_guard`` to pin that a
        kill-and-migrate run on warmed workers mints ZERO new
        compilations (migration reuses the existing
        extract/insert/decode programs)."""
        out: Dict[str, Callable] = {"migrate_extract": self._migrate_extract}
        for w in self.prefill_workers:
            out[f"{w.name}.chunk_prefill"] = w._chunk_prefill
            out[f"{w.name}.extract"] = w._extract
        for w in self.decode_workers:
            for k, fn in w.engine.programs().items():
                if fn is not None:
                    out[f"{w.name}.{k}"] = fn
            out[f"{w.name}.insert"] = w._insert
        return out

    # -- stats -------------------------------------------------------------
    def occupancy(self) -> float:
        """Occupied / total decode slots over the ALIVE fleet (the
        autoscale gauge — dead capacity is not capacity)."""
        alive = self.alive_decode_workers()
        tot = sum(w.engine.serve_cfg.num_slots for w in alive)
        occ = sum(sum(s is not None for s in w.engine._slots)
                  for w in alive)
        return occ / tot if tot else 0.0

    def stats(self) -> Dict[str, Any]:
        """One JSON-serializable snapshot of the whole cluster: router
        admission/shed accounting, transfer wire totals, membership and
        elastic counters, merged decode latency quantiles and the summed
        goodput-under-SLO report — ``shed_rate`` / ``admitted_rps`` /
        ``transfer_ms_p50`` plus the chaos-gated ``migrations_total`` /
        ``replayed_tokens`` / ``worker_deaths`` / ``heartbeat_misses`` /
        ``transfer_retries`` are the flat headline fields
        ``monitor.regress`` gates."""
        router_stats = self.router.stats()
        out: Dict[str, Any] = {
            "hosts": {"prefill": len(self.prefill_workers),
                      "decode": len(self.decode_workers)},
            "steps": self._step_idx,
            "completed": self.completed,
            "generated_tokens": sum(
                w.engine._tokens_generated for w in self.decode_workers),
            "occupancy": self.occupancy(),
            "router": router_stats,
            "shed_rate": router_stats["shed_rate"],
        }
        # admitted requests per second of cluster wall time (elapsed on
        # the shared clock since the first submission)
        elapsed_ms = (self._now_ms() - self._t_first_submit_ms
                      if self._t_first_submit_ms is not None else 0.0)
        out["admitted_rps"] = (
            round(self.router.admitted / (elapsed_ms / 1e3), 4)
            if elapsed_ms > 0 else None)
        tr = self.transport
        out["transfer"] = {
            "transfers": tr.transfers_total,
            "wire_bytes_total": tr.wire_bytes_total,
            "transfer_ms_total": round(tr.transfer_ms_total, 3),
            "wire_mode": self.cluster_cfg.wire_mode,
            "bytes_per_transfer": (
                tr.wire_bytes_total // tr.transfers_total
                if tr.transfers_total else None),
            "bytes_per_ms": (
                round(tr.wire_bytes_total / tr.transfer_ms_total, 1)
                if tr.transfer_ms_total > 0 else None),
            "in_flight": tr.in_flight,
            "faults": {"drops": tr.drops_total, "stalls": tr.stalls_total,
                       "corrupts": tr.corrupts_total},
        }
        # the elastic ledger + flat chaos-gate headline fields
        out["membership"] = self.membership.stats()
        out["elastic"] = {
            "migrations_total": self.migrations_total,
            "replayed_tokens": sum(
                w.replayed_tokens for w in self.decode_workers),
            "transfer_retries": self.transfer_retries,
            "transfer_crc_failures": self.transfer_crc_failures,
            "transfer_timeouts": self.transfer_timeouts,
            "transfer_failed": self.transfer_failed,
            "duplicates_ignored": self.duplicates_ignored,
        }
        out["migrations_total"] = self.migrations_total
        out["replayed_tokens"] = out["elastic"]["replayed_tokens"]
        out["worker_deaths"] = self.membership.worker_deaths
        out["heartbeat_misses"] = self.membership.heartbeat_misses
        out["transfer_retries"] = self.transfer_retries
        # the per-tenant adapter plane: catalog + warm-dispatch ledger
        # (adapter_hit_rate / adapter_warm_dispatch_rate higher-better,
        # adapter_load_ms / adapter_evictions lower-better — all four
        # are monitor.regress polarity entries)
        if self.cluster_cfg.serve.lora_rank > 0:
            regs = [w.engine.adapters for w in self.decode_workers
                    if w.engine.adapters is not None]
            hits = sum(r.hits_total for r in regs)
            misses = sum(r.misses_total for r in regs)
            out["adapters"] = {
                "catalog": self.adapter_catalog(),
                "rank": self.cluster_cfg.serve.lora_rank,
                "max_adapters": self.cluster_cfg.serve.max_adapters,
                "catalog_loads": self.adapter_loads,
                "hits": hits,
                "misses": misses,
                "evictions": sum(r.evictions_total for r in regs),
                "warm_dispatches": self.router.adapter_warm_dispatches,
                "cold_dispatches": self.router.adapter_cold_dispatches,
            }
            out["adapter_hit_rate"] = (
                round(hits / (hits + misses), 4)
                if (hits + misses) else None)
            out["adapter_evictions"] = out["adapters"]["evictions"]
            out["adapter_warm_dispatch_rate"] = router_stats[
                "adapter_warm_dispatch_rate"]
            out["adapter_load_ms"] = round(
                sum(w.engine._adapter_load_ms_total
                    for w in self.decode_workers), 3)
        h = self.transfer_ms_hist
        if h.total:
            out["transfer_ms_p50"] = round(h.quantile(0.5), 4)
            out["transfer_ms_p99"] = round(h.quantile(0.99), 4)
        # merged decode-side latency quantiles: the per-worker streaming
        # histograms are associative — merging them equals one engine
        # having seen every retirement
        for dim in ("ttft_ms", "tpot_ms", "queue_ms", "e2e_ms",
                    "decode_step_ms"):
            merged = None
            for w in self.decode_workers:
                hw = w.engine.hists[dim]
                merged = hw if merged is None else merged.merge(hw)
            if merged is not None and merged.total:
                out[f"{dim}_p50"] = round(merged.quantile(0.5), 3)
                out[f"{dim}_p99"] = round(merged.quantile(0.99), 3)
        # summed SLO/goodput accounting across decode hosts
        reports = [w.engine._slo.report() for w in self.decode_workers
                   if w.engine._slo is not None]
        if reports:
            slo_rep: Dict[str, Any] = {
                "completed": sum(r["completed"] for r in reports),
                "good": sum(r["good"] for r in reports),
                "goodput_rps": round(
                    sum(r["goodput_rps"] for r in reports), 4),
                "throughput_rps": round(
                    sum(r["throughput_rps"] for r in reports), 4),
                "violations": {
                    k: sum(r["violations"].get(k, 0) for r in reports)
                    for k in reports[0]["violations"]},
                "slo": reports[0]["slo"],
            }
            comp = slo_rep["completed"]
            slo_rep["good_fraction"] = (round(slo_rep["good"] / comp, 4)
                                        if comp else None)
            out["slo_report"] = slo_rep
            out["goodput_rps"] = slo_rep["goodput_rps"]
            out["good_fraction"] = slo_rep["good_fraction"]
            # the fleet roll-up alias (regress-gated higher-is-better):
            # cluster-wide goodput as the scrape/alert plane reports it
            out["fleet_goodput_rps"] = slo_rep["goodput_rps"]
        # performance forensics (monitor tier 4): the event-derived
        # per-component decomposition and the per-tenant ledger, with
        # the flat regress-gated duals (attrib_coverage /
        # {c}_component_ms_* / cost_per_token / cost_per_request /
        # meter_coverage) hoisted next to the other headline fields
        if self.attrib is not None:
            att = self.attrib.summary()
            out["attribution"] = att
            if att.get("attrib_coverage") is not None:
                out["attrib_coverage"] = att["attrib_coverage"]
            for c in ("queue", "prefill", "transfer", "decode", "stall"):
                for q in ("p50", "p99"):
                    k = f"{c}_component_ms_{q}"
                    if att.get(k) is not None:
                        out[k] = att[k]
        if self.meter is not None:
            m = self.meter.stats(completed=self.completed)
            m["worker_cost_rates"] = self.meter.worker_rates(
                self._now_ms())
            out["meter"] = m
            out["cost_per_token"] = m["cost_per_token"]
            out["cost_per_request"] = m["cost_per_request"]
            out["meter_coverage"] = m["meter_coverage"]
        out["prefill_hosts"] = [
            {"host": w.name, "state": self._state(w.name),
             "chunks_run": w.chunks_run,
             "prefills_done": w.prefills_done,
             "backlog_tokens": w.backlog_tokens}
            for w in self.prefill_workers]
        out["decode_hosts"] = [
            {"host": w.name, "state": self._state(w.name),
             "completed": w.engine.completed,
             "handoffs_admitted": w.admitted,
             "handoffs_pending": len(w._pending),
             "migrations_in": w.migrations_in,
             "migrations_out": w.migrations_out,
             "occupancy": w.engine.occupancy()}
            for w in self.decode_workers]
        # the fleet observability plane's own accounting (monitor tier
        # 3): scrape cost/coverage, alert ledger, flight-ring fill —
        # flat headline duals (alerts_fired_total / scrape_ms /
        # scrape_coverage / trace stitch) are regress-gated
        fleet: Dict[str, Any] = dict(self.scraper.stats())
        fleet["alerts"] = self._alerts.stats()
        fleet["traces_minted"] = self._trace_seq
        if self._flight_cluster is not None:
            fleet["flight"] = {
                name: {"records": len(ring),
                       "dropped_records": ring.dropped_records,
                       "dumps": ring.dumps_total}
                for name, ring in sorted(self._flight_rings().items())}
        out["fleet"] = fleet
        out["alerts_fired_total"] = self._alerts.alerts_fired_total
        if self.scraper.last_coverage is not None:
            out["scrape_coverage"] = self.scraper.last_coverage
        if self.scraper.scrape_ms_hist.total:
            out["scrape_ms_p50"] = fleet.get("scrape_ms_p50")
        if self._chaos is not None:
            out["chaos"] = self._chaos.summary()
        return out

