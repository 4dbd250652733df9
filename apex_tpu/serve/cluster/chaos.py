"""Deterministic cluster fault injection — the chaos harness the elastic
serving claims are proven against.

``resilience.chaos`` gave the TRAINING recovery paths their failures
(NaN at step k, torn checkpoints, preempt at step k); this module is the
same discipline for the serving cluster. Every fault is step-keyed on
the cluster tick counter — no randomness, no wall time — so a chaos run
is exactly reproducible and its streams can be pinned BITWISE against
the fault-free run:

* :class:`KillWorker` — fail-stop a worker at tick k: immediately dead
  (no drain), its in-flight requests migrate (decode) or re-enqueue at
  the router (prefill). Models a host crash with a reachable HBM / a
  reclaim with a grace window.
* :class:`PreemptWorker` — deliver a preemption at tick k THROUGH the
  worker's :class:`~apex_tpu.resilience.preemption.PreemptionHandler`
  (the exact code path a real SIGTERM takes, minus the kernel): the
  worker drains — prefill finishes or re-enqueues its staged prompts,
  decode proactively migrates — then leaves.
* :class:`StallWorker` — the worker stops making progress (and beating)
  for N ticks: the heartbeat-miss detector (or a per-worker
  :class:`~apex_tpu.resilience.preemption.StallWatchdog`) must notice
  and declare it dead so its requests migrate.
* :class:`DropTransfer` / :class:`StallLink` / :class:`CorruptTransfer`
  — the link faults, injected into the cluster's
  :class:`~apex_tpu.serve.cluster.transfer.SimTransport` at tick k: the
  next ``count`` sends are eaten / delayed ``stall_ms`` / bit-rotted.
  Detection is the receiver's job (CRC + timeout), retry with backoff
  is the cluster's; the stream must still land bitwise.

``ServeCluster(chaos=ClusterChaos([...]))`` consults the plan at the
top of every tick.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Sequence, Tuple

__all__ = ["ClusterChaos", "CorruptTransfer", "DropTransfer",
           "KillWorker", "PreemptWorker", "StallLink", "StallWorker"]


@dataclasses.dataclass(frozen=True)
class KillWorker:
    """Fail-stop ``worker`` at cluster tick ``at_step``."""

    at_step: int
    worker: str


@dataclasses.dataclass(frozen=True)
class PreemptWorker:
    """Trigger ``worker``'s PreemptionHandler at tick ``at_step`` (the
    SIGTERM path → drain protocol)."""

    at_step: int
    worker: str


@dataclasses.dataclass(frozen=True)
class StallWorker:
    """``worker`` makes no progress (and sends no heartbeat) for
    ``for_steps`` ticks starting at ``at_step`` (forever when 0) — the
    wedged-host failure the heartbeat/watchdog path must catch."""

    at_step: int
    worker: str
    for_steps: int = 0


@dataclasses.dataclass(frozen=True)
class DropTransfer:
    """The next ``count`` link sends after tick ``at_step`` are eaten."""

    at_step: int
    count: int = 1


@dataclasses.dataclass(frozen=True)
class StallLink:
    """The next ``count`` link sends are delayed ``stall_ms``."""

    at_step: int
    stall_ms: float
    count: int = 1


@dataclasses.dataclass(frozen=True)
class CorruptTransfer:
    """The next ``count`` link sends arrive bit-rotted (CRC must catch
    them)."""

    at_step: int
    count: int = 1


_FAULT_TYPES = (KillWorker, PreemptWorker, StallWorker, DropTransfer,
                StallLink, CorruptTransfer)


class ClusterChaos:
    """An ordered, deterministic fault plan. The cluster calls
    :meth:`apply` once per tick; each fault fires exactly once, at the
    first tick >= its ``at_step``. ``fired`` keeps the (tick, fault)
    ledger for the chaos record."""

    def __init__(self, faults: Sequence[Any]):
        for f in faults:
            if not isinstance(f, _FAULT_TYPES):
                raise TypeError(f"not a cluster fault: {f!r}")
            if f.at_step < 0:
                raise ValueError(f"at_step must be >= 0: {f!r}")
        self._pending: List[Any] = sorted(faults, key=lambda f: f.at_step)
        self.fired: List[Tuple[int, Any]] = []

    @property
    def pending(self) -> int:
        return len(self._pending)

    def apply(self, cluster, step_idx: int) -> List[Any]:
        """Fire every not-yet-fired fault whose ``at_step`` has arrived;
        returns the faults fired this tick."""
        fired_now: List[Any] = []
        while self._pending and self._pending[0].at_step <= step_idx:
            f = self._pending.pop(0)
            self._fire(cluster, f, step_idx)
            self.fired.append((step_idx, f))
            fired_now.append(f)
        return fired_now

    def _fire(self, cluster, f: Any, step_idx: int) -> None:
        if isinstance(f, KillWorker):
            cluster.kill_worker(f.worker)
        elif isinstance(f, PreemptWorker):
            cluster.preempt_worker(f.worker)
        elif isinstance(f, StallWorker):
            if f.for_steps == 0 and (
                    cluster.cluster_cfg.heartbeat_timeout_ms is None
                    and cluster.cluster_cfg.watchdog_timeout_ms is None):
                # a forever-stall is only DETECTABLE by heartbeat or
                # watchdog; with neither armed, the worker's requests
                # would hang forever — fail the configuration loudly
                raise ValueError(
                    "StallWorker(for_steps=0) needs heartbeat_timeout_ms "
                    "or watchdog_timeout_ms set: a wedged worker is only "
                    "detected by those paths")
            cluster.stall_worker(f.worker, f.for_steps)
        elif isinstance(f, DropTransfer):
            if cluster.cluster_cfg.transfer_timeout_ms is None:
                # a drop is only DETECTABLE through the timeout path —
                # injecting one into a cluster that cannot notice would
                # hang the stream forever; fail the configuration loudly
                raise ValueError(
                    "DropTransfer needs ClusterConfig.transfer_timeout_ms "
                    "set: a dropped send is only detected by timeout")
            cluster.transport.inject_fault("drop", count=f.count)
        elif isinstance(f, StallLink):
            cluster.transport.inject_fault("stall", count=f.count,
                                           stall_ms=f.stall_ms)
        elif isinstance(f, CorruptTransfer):
            cluster.transport.inject_fault("corrupt", count=f.count)

    def summary(self) -> List[Dict[str, Any]]:
        """JSON-ready ledger of fired faults (for the bench record)."""
        return [{"step": step, "fault": type(f).__name__,
                 **dataclasses.asdict(f)} for step, f in self.fired]
