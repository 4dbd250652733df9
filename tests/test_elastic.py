"""Elastic fault-tolerant training acceptance suite (reshard + supervisor
+ sentinels + training chaos).

Gates: (1) the reshard arithmetic round-trips a dp=N block-aligned flat
layout through every dp degree in {1,2,4,8} BITWISE (the concatenated
global layout is dp-independent except trailing zero padding) and refuses
manifest lies, non-zero tails, indivisible shard multiples, and
gap/overlap placement sets loudly; (2) a dp=4 checkpoint saved with an
``elastic=`` spec (masters, Adam moments, EF residuals) restores at
dp∈{1,2,8} with ``allow_reshard=True`` — bitwise leaf parity for flat
leaves, rank-sum conservation for stacked EF residuals — and the SAME
restore without the flag still raises the fingerprint ``CheckpointError``;
(3) the TrainSupervisor's retry/skip→rollback→halt ladder, preemption
exit, and chaos kill→elastic-resume-at-a-different-dp all run on a manual
clock, and the resumed loss curve rejoins the fault-free run bitwise (the
sim optimizer is elementwise, so the padded-flat math is dp-invariant);
(4) the straggler/SDC sentinels flag injected faults with zero false
positives on a clean run — mesh rows under the shard_map shim.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.contrib.optimizers._sharding import shard_size
from apex_tpu.parallel.mesh import DP_AXIS, build_mesh
from apex_tpu.resilience import (
    AnomalyHalted,
    CheckpointError,
    CheckpointManager,
    CorruptShardFile,
    GuardPolicy,
    KillRankAtStep,
    PreemptionHandler,
    ReshardError,
    SDCSentinel,
    SlowRank,
    StragglerSentinel,
    TrainChaosPlan,
    TrainSupervisor,
    dp_flat_spec,
    dp_stacked_spec,
    grad_checksum,
    legal_resume_degrees,
    load_state_dict,
    replicated_spec,
    state_dict,
)
from apex_tpu.resilience import chaos
from apex_tpu.resilience.reshard import (
    LeafSpec,
    assemble_leaf,
    elastic_manifest,
    reshard_flat,
    reshard_stacked,
    retarget_leaf,
)
from apex_tpu.resilience.supervisor import RESTART_NAME


DEGREES = (1, 2, 4, 8)
_N, _MULT = 13, 2  # odd logical size + alignment: padding differs per dp


# ---------------------------------------------------------------------------
# reshard arithmetic (stock-safe, pure numpy)


def test_reshard_flat_round_trips_all_degrees():
    base = np.arange(1, _N + 1, dtype=np.float32)
    for dp_a in DEGREES:
        flat_a = np.zeros(shard_size(_N, dp_a, _MULT) * dp_a, np.float32)
        flat_a[:_N] = base
        for dp_b in DEGREES:
            flat_b = reshard_flat(flat_a, _N, dp_b, multiple=_MULT)
            assert flat_b.size == shard_size(_N, dp_b, _MULT) * dp_b
            np.testing.assert_array_equal(flat_b[:_N], base)
            assert not flat_b[_N:].any()  # padding stays zero
            back = reshard_flat(flat_b, _N, dp_a, multiple=_MULT)
            np.testing.assert_array_equal(back, flat_a)  # bitwise


def test_reshard_flat_refuses_bad_inputs():
    # non-zero tail past n means the manifest's n is a lie
    with pytest.raises(ReshardError):
        reshard_flat(np.ones(8, np.float32), 5, 2)
    # stored buffer shorter than the logical size
    with pytest.raises(ReshardError):
        reshard_flat(np.zeros(4, np.float32), 5, 2)


def test_reshard_stacked_grow_shrink_conserves_rank_sum():
    stacked = np.arange(1, 9, dtype=np.float32).reshape(4, 2)
    np.testing.assert_array_equal(reshard_stacked(stacked, 4), stacked)
    grown = reshard_stacked(stacked, 8)
    assert grown.shape == (8, 2)
    np.testing.assert_array_equal(grown[:4], stacked)
    assert not grown[4:].any()  # new ranks start with zero residual
    # grow-then-shrink folds the zero rows away: bitwise original
    np.testing.assert_array_equal(reshard_stacked(grown, 4), stacked)
    shrunk = reshard_stacked(stacked, 2)
    assert shrunk.shape == (2, 2)
    # the EF convergence quantity is the rank-SUM of residuals
    np.testing.assert_array_equal(shrunk.sum(0), stacked.sum(0))


def test_retarget_leaf_refusals():
    spec = dp_flat_spec(_N, 4, _MULT)
    stored = np.zeros(shard_size(_N, 4, _MULT) * 4, np.float32)
    # replicated leaves must not change shape under reshard
    with pytest.raises(ReshardError):
        retarget_leaf(np.zeros((3,)), replicated_spec(), (4,))
    # dp_flat lives are 1-D by construction
    with pytest.raises(ReshardError):
        retarget_leaf(stored, spec, (4, 4))
    # manifest arithmetic lie: stored size != shard_size(n,dp,mult)*dp
    with pytest.raises(ReshardError):
        retarget_leaf(stored[:-2], spec, (16,))
    # live layout not a multiple of the shard alignment
    with pytest.raises(ReshardError, match="shard_multiple arithmetic"):
        retarget_leaf(stored, spec, (15,))


def test_assemble_leaf_round_trip_and_refusals():
    full = np.arange(8, dtype=np.float32)
    got = assemble_leaf((8,), np.float32, {"0:4": full[:4], "4:8": full[4:]})
    np.testing.assert_array_equal(got, full)
    # 2-D placements (the per-shard manifest's index keys are per-dim)
    sq = np.arange(16, dtype=np.float32).reshape(4, 4)
    got2 = assemble_leaf((4, 4), np.float32,
                         {"0:2,0:4": sq[:2], "2:4,0:4": sq[2:]})
    np.testing.assert_array_equal(got2, sq)
    with pytest.raises(ReshardError, match="overlap"):
        assemble_leaf((8,), np.float32,
                      {"0:4": full[:4], "2:6": full[2:6]})
    with pytest.raises(ReshardError, match="missing"):
        assemble_leaf((8,), np.float32, {"0:4": full[:4]})
    with pytest.raises(ReshardError, match="dims"):
        assemble_leaf((8,), np.float32, {"0:4,0:1": full[:4].reshape(4, 1)})


def test_legal_resume_degrees():
    # n=13, mult=2: at dp=8 every rank owns 2 slots but rank 7 starts at
    # 14 > 13 — all padding, so 8 is illegal
    specs = {"0": dataclasses.asdict(dp_flat_spec(_N, 4, _MULT))}
    assert legal_resume_degrees(specs, candidates=DEGREES) == [1, 2, 4]
    # a big leaf keeps every candidate legal
    big = {"0": dataclasses.asdict(dp_flat_spec(1 << 20, 4, 256))}
    assert legal_resume_degrees(big, candidates=DEGREES) == list(DEGREES)
    # no dp_flat leaves -> nothing constrains the topology
    free = {"0": dataclasses.asdict(replicated_spec()),
            "1": dataclasses.asdict(dp_stacked_spec(4))}
    assert legal_resume_degrees(free, candidates=DEGREES) == list(DEGREES)


def test_elastic_manifest_forms():
    state = {"a": jnp.zeros((3,)), "b": jnp.zeros(())}
    spec = {"a": dp_flat_spec(3, 1), "b": replicated_spec()}
    m = elastic_manifest(state, spec)
    assert set(m) == {"0", "1"} and m["0"]["kind"] == "dp_flat"
    # an already-flat digit-keyed mapping passes through validated
    assert elastic_manifest(state, m) == m
    # leaf-count mismatch is refused (spec tree from a different state)
    with pytest.raises((ReshardError, ValueError)):
        elastic_manifest(state, {"a": dp_flat_spec(3, 1)})
    with pytest.raises(ValueError):
        LeafSpec(kind="diagonal")


# ---------------------------------------------------------------------------
# elementwise-Adam sim: the padded-flat math is dp-invariant, so every
# cross-degree restore must continue the loss curve BITWISE


def _flat_layout(dp):
    return shard_size(_N, dp, _MULT) * dp


def _sim_init(dp):
    """dp-flat padded Adam state over one logical 13-element param, plus
    a stacked per-rank EF-residual-style leaf."""
    size = _flat_layout(dp)
    master = np.zeros(size, np.float32)
    master[:_N] = np.linspace(-1.0, 1.0, _N, dtype=np.float32)
    state = {
        "count": jnp.zeros((), jnp.int32),
        "master": jnp.asarray(master),
        "mu": jnp.zeros(size, jnp.float32),
        "nu": jnp.zeros(size, jnp.float32),
        "ef": jnp.zeros((dp, 3), jnp.float32),
    }
    spec = {
        "count": replicated_spec(),
        "master": dp_flat_spec(_N, dp, _MULT),
        "mu": dp_flat_spec(_N, dp, _MULT),
        "nu": dp_flat_spec(_N, dp, _MULT),
        "ef": dp_stacked_spec(dp),
    }
    return state, spec


_TARGET = np.linspace(1.0, 2.0, _N, dtype=np.float32)


def _sim_step(state, losses=None):
    """One elementwise Adam step on the padded flat layout. Padded slots
    see zero grads and stay zero, so the [0:n) math is identical at every
    dp degree — elementwise float32 ops make it bitwise-identical."""
    master = np.asarray(state["master"])
    mu, nu = np.asarray(state["mu"]), np.asarray(state["nu"])
    w = master[:_N]
    g_log = w - _TARGET
    if losses is not None:
        losses.append(0.5 * float(np.dot(g_log, g_log)))
    g = np.zeros_like(master)
    g[:_N] = g_log
    t = int(state["count"]) + 1
    mu = np.float32(0.9) * mu + np.float32(0.1) * g
    nu = np.float32(0.999) * nu + np.float32(0.001) * (g * g)
    mhat = mu / np.float32(1.0 - 0.9 ** t)
    vhat = nu / np.float32(1.0 - 0.999 ** t)
    master = master - np.float32(0.1) * mhat / (np.sqrt(vhat)
                                                + np.float32(1e-8))
    return {"count": jnp.int32(t), "master": jnp.asarray(master),
            "mu": jnp.asarray(mu), "nu": jnp.asarray(nu),
            "ef": state["ef"]}


def test_elastic_restore_across_degrees_bitwise(tmp_path):
    state, spec = _sim_init(4)
    for _ in range(3):  # non-trivial moments before the save
        state = _sim_step(state)
    state["ef"] = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(state, 3, block=True, elastic=spec)
    manifest = json.load(open(os.path.join(mgr.step_path(3),
                                           "manifest.json")))
    # flat leaf order is the sorted-key treedef: count, ef, master, mu, nu
    assert manifest["elastic"]["2"]["kind"] == "dp_flat"
    assert manifest["elastic"]["1"]["kind"] == "dp_stacked"
    for dp_new in (1, 2, 8):
        template, _ = _sim_init(dp_new)
        got, step = mgr.restore(target=template, allow_reshard=True)
        assert step == 3
        assert mgr.last_reshard_ms > 0.0
        for k in ("master", "mu", "nu"):
            flat = np.asarray(got[k])
            assert flat.size == _flat_layout(dp_new)
            np.testing.assert_array_equal(
                flat[:_N], np.asarray(state[k])[:_N])  # bitwise
            assert not flat[_N:].any()
        # stacked EF residuals conserve the rank-sum at every degree
        np.testing.assert_array_equal(
            np.asarray(got["ef"]).sum(0), np.asarray(state["ef"]).sum(0))
        assert got["ef"].shape == (dp_new, 3)
        assert int(got["count"]) == int(state["count"])


def test_elastic_restore_without_flag_still_refused(tmp_path):
    state, spec = _sim_init(4)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(state, 1, block=True, elastic=spec)
    template, _ = _sim_init(2)
    with pytest.raises(CheckpointError):
        mgr.restore(target=template)  # fingerprint refusal survives
    # same-topology restores never pay the reshard path
    same, _ = _sim_init(4)
    got, _ = mgr.restore(target=same)
    np.testing.assert_array_equal(np.asarray(got["master"]),
                                  np.asarray(state["master"]))


def test_resave_at_new_degree_restores_at_old_bitwise(tmp_path):
    state, spec4 = _sim_init(4)
    for _ in range(2):
        state = _sim_step(state)
    mgr = CheckpointManager(str(tmp_path), allow_reshard=True)
    mgr.save(state, 2, block=True, elastic=spec4)
    template2, spec2 = _sim_init(2)
    at2, _ = mgr.restore(target=template2)  # ctor-level opt-in
    mgr.save(at2, 4, block=True, elastic=spec2)
    template4, _ = _sim_init(4)
    back, step = mgr.restore(target=template4)
    assert step == 4
    for k in ("master", "mu", "nu"):  # leaf-for-leaf identical
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(state[k]))


def test_state_dict_elastic_round_trip():
    state, spec = _sim_init(4)
    state = _sim_step(state)
    d = state_dict(state, elastic=spec)
    assert set(d["elastic"]) == {str(i) for i in range(5)}
    template, _ = _sim_init(2)
    got = load_state_dict(template, d, allow_reshard=True)
    np.testing.assert_array_equal(np.asarray(got["master"])[:_N],
                                  np.asarray(state["master"])[:_N])
    with pytest.raises(CheckpointError):
        load_state_dict(template, d)  # no flag -> fingerprint refusal


def test_optimizer_elastic_specs():
    from apex_tpu.comm import CompressionConfig
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.fsdp import FSDP, FSDPAdam

    params = {"w": jnp.zeros((5, 3)), "b": jnp.zeros((7,))}
    opt = DistributedFusedAdam(lr=1e-3)
    spec = opt.elastic_spec(params, 4)
    assert spec.count.kind == "replicated"
    assert spec.master["w"] == dp_flat_spec(15, 4, spec.master["w"].multiple)
    assert spec.mu["b"].n == 7 and spec.nu["b"].dp == 4
    assert opt.elastic_comm_spec(params, 4) is None  # no EF residuals
    ef = DistributedFusedAdam(
        lr=1e-3, compression=CompressionConfig("int8_ef", min_elements=1))
    comm = ef.elastic_comm_spec(params, 4)
    assert comm["w"] == dp_stacked_spec(4)
    fopt = FSDPAdam(fsdp=FSDP())
    fspec = fopt.elastic_spec(params, 2)
    assert fspec.master["w"].multiple == FSDP().shard_multiple
    assert fspec.count.kind == "replicated"


# ---------------------------------------------------------------------------
# sharded checkpoint dirs: on-disk per-shard reshard + chaos corruption
# (stock-safe: forced predicate on the single-process mesh, test_fsdp's
# fixture idiom)


@pytest.fixture
def sharded_ckpt(monkeypatch, tmp_path):
    """Force the cross-process predicate for dp-sharded (64,) leaves so
    the per-shard path runs on this single-process mesh."""
    from apex_tpu.resilience import checkpoint as ck

    monkeypatch.setattr(
        ck, "_is_cross_process",
        lambda a: hasattr(a, "addressable_shards") and getattr(
            a, "shape", ()) == (64,))
    from jax.sharding import NamedSharding

    mesh = build_mesh(tp=1, pp=1, sp=1)
    x = jax.device_put(jnp.arange(64, dtype=jnp.float32),
                       NamedSharding(mesh, P("dp")))
    state = {"w": x, "b": jnp.ones((3,))}
    return ck, str(tmp_path), state, x


def test_sharded_elastic_restore_onto_new_dp_degree(sharded_ckpt):
    """A dp=8 per-shard checkpoint (8 placements of 8) reassembles and
    rebinds onto a dp=2 mesh's layout (2 shards of 32) under
    allow_reshard=True; without the flag the PR-9 skew refusal stands."""
    ck, d, state, x = sharded_ckpt
    spec = {"w": dp_flat_spec(64, 8), "b": replicated_spec()}
    mgr = ck.CheckpointManager(d)
    mgr.save(state, 7, block=True, elastic=spec)
    from jax.sharding import NamedSharding

    mesh2 = build_mesh(tp=4, pp=1, sp=1)  # dp=2
    y = jax.device_put(jnp.zeros(64, dtype=jnp.float32),
                       NamedSharding(mesh2, P("dp")))
    template = {"w": y, "b": jnp.zeros((3,))}
    with pytest.raises(ck.CheckpointError, match="skew"):
        mgr.restore(target=template)
    got, step = mgr.restore(target=template, allow_reshard=True)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(x))
    assert got["w"].sharding == y.sharding  # rebound onto the LIVE layout
    assert mgr.last_reshard_ms > 0.0


def test_corrupt_shard_dir_detected_and_skipped(sharded_ckpt):
    """chaos.corrupt_checkpoint(shard=K) reaches inside a sharded
    checkpoint's per-process dir; the damage is detectable (verify False)
    and latest_valid() falls back to the older good step."""
    ck, d, state, x = sharded_ckpt
    mgr = ck.CheckpointManager(d)
    mgr.save(state, 1, block=True)
    mgr.save(state, 2, block=True)
    chaos.corrupt_checkpoint(mgr.step_path(2), part="payload", mode="flip",
                             shard=0)
    assert not mgr.verify(mgr.step_path(2))
    assert mgr.latest_valid() == mgr.step_path(1)
    # a shard dir that does not exist would be an undetectable fault
    with pytest.raises(FileNotFoundError, match="undetectable"):
        chaos.corrupt_checkpoint(mgr.step_path(1), shard=3)


# ---------------------------------------------------------------------------
# TrainSupervisor: chaos kill -> elastic resume rejoins bitwise; manual
# clock for retry/escalation/preemption (no real sleeps)


def test_chaos_kill_then_elastic_resume_rejoins_bitwise(tmp_path):
    # fault-free reference at dp=4
    ref_losses = []
    state, _ = _sim_init(4)
    for _ in range(8):
        state = _sim_step(state, ref_losses)

    # run A: dp=4 under the supervisor, killed by chaos at step 5
    losses_a = []
    state_a, spec4 = _sim_init(4)
    mgr = CheckpointManager(str(tmp_path))
    plan = TrainChaosPlan([KillRankAtStep(at_step=5)])
    sup_a = TrainSupervisor(
        lambda st, i: _sim_step(st, losses_a), mgr, elastic=spec4,
        dp_degree=4, save_freq=2, chaos=plan,
        clock=iter(np.arange(1e6)).__next__, sleep=lambda s: None)
    _, stopped = sup_a.run(state_a, 0, 8)
    assert sup_a.exited == "killed" and stopped == 5
    assert plan.summary() == [{"step": 5, "fault": "KillRankAtStep",
                               "at_step": 5, "rank": 0}]
    info = TrainSupervisor.read_restart(str(tmp_path))
    assert info["reason"] == "killed" and info["allow_reshard"]
    assert info["checkpoint"] == mgr.step_path(4)
    assert info["legal_resume_dp"] == [1, 2, 4]  # dp=8 would be all-padding

    # run B: resume at dp=2 from the restart manifest, finish the run
    losses_b = []
    template, spec2 = _sim_init(2)
    mgr2 = CheckpointManager(str(tmp_path), allow_reshard=True)
    sup_b = TrainSupervisor(
        lambda st, i: _sim_step(st, losses_b), mgr2, elastic=spec2,
        dp_degree=2, clock=iter(np.arange(1e6)).__next__,
        sleep=lambda s: None)
    state_b, start = sup_b.resume(template)
    assert start == 4 and sup_b.counters["elastic_resumes_total"] == 1
    _, done = sup_b.run(state_b, start, 8 - start)
    assert sup_b.exited == "completed" and done == 8
    # the stitched curve rejoins the fault-free one BITWISE
    assert losses_a[:4] + losses_b == ref_losses


def test_resume_at_illegal_degree_refused(tmp_path):
    state, spec4 = _sim_init(4)
    mgr = CheckpointManager(str(tmp_path))
    sup = TrainSupervisor(lambda st, i: _sim_step(st), mgr, elastic=spec4,
                          dp_degree=4, save_freq=1)
    sup.run(state, 0, 2)
    template, spec8 = _sim_init(8)
    sup8 = TrainSupervisor(lambda st, i: _sim_step(st),
                           CheckpointManager(str(tmp_path),
                                             allow_reshard=True),
                           elastic=spec8, dp_degree=8)
    with pytest.raises(ValueError, match="legal resume degree"):
        sup8.resume(template)


def test_supervisor_retries_transients_with_backoff():
    sleeps, fails = [], {"left": 2}

    def flaky(state, step):
        if fails["left"]:
            fails["left"] -= 1
            raise RuntimeError("transient collective timeout")
        return _sim_step(state)

    sup = TrainSupervisor(flaky, None, dp_degree=1, max_retries=2,
                          backoff_s=0.5, clock=iter(np.arange(1e6)).__next__,
                          sleep=sleeps.append)
    state, _ = _sim_init(1)
    _, nxt = sup.run(state, 0, 1)
    assert nxt == 1 and sup.exited == "completed"
    assert sup.counters["retries_total"] == 2
    assert sleeps == [0.5, 1.0]  # exponential backoff


def test_supervisor_escalation_ladder_skip_rollback_halt(tmp_path):
    state, spec = _sim_init(1)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(state, 0, block=True, elastic=spec)

    def poisoned(st, step):
        raise RuntimeError("persistent desync")

    sup = TrainSupervisor(
        poisoned, mgr, elastic=spec, dp_degree=1, max_retries=0,
        policy=GuardPolicy(on_anomaly="skip", skip_budget=1,
                           rollback_budget=1),
        clock=iter(np.arange(1e6)).__next__, sleep=lambda s: None)
    with pytest.raises(AnomalyHalted):
        sup.run(state, 0, 10)
    assert sup.counters["skips_total"] == 1
    assert sup.counters["rollbacks_total"] == 1
    assert TrainSupervisor.read_restart(str(tmp_path))["reason"] == "halted"


def test_supervisor_preemption_synchronized_save_and_exit(tmp_path):
    handler = PreemptionHandler(install=False)
    state, spec = _sim_init(1)
    mgr = CheckpointManager(str(tmp_path))

    def step_fn(st, step):
        if step == 2:
            handler.trigger()  # the SIGTERM body, minus the kernel
        return _sim_step(st)

    sup = TrainSupervisor(step_fn, mgr, elastic=spec, dp_degree=1,
                          preemption=handler,
                          clock=iter(np.arange(1e6)).__next__,
                          sleep=lambda s: None)
    _, nxt = sup.run(state, 0, 10)
    assert sup.exited == "preempted" and nxt == 4
    assert mgr.latest_valid() is not None
    info = TrainSupervisor.read_restart(str(tmp_path))
    assert info["reason"] == "preempted" and info["step"] == 4
    # the saved state resumes exactly where the grace-window save left it
    got, step = mgr.restore(target=_sim_init(1)[0])
    assert step == 4 and int(got["count"]) == 3  # steps 0,1,2 ran


def test_chaos_plan_validation_and_slow_rank_flags():
    with pytest.raises(TypeError):
        TrainChaosPlan([object()])
    with pytest.raises(ValueError, match="at_step"):
        TrainChaosPlan([KillRankAtStep(at_step=-1)])
    # CorruptShardFile before any durable save is undetectable -> loud
    sup = TrainSupervisor(lambda st, i: _sim_step(st), None, dp_degree=1,
                          chaos=TrainChaosPlan([CorruptShardFile(at_step=0)]),
                          clock=iter(np.arange(1e6)).__next__,
                          sleep=lambda s: None)
    with pytest.raises(ValueError, match="no valid checkpoint"):
        sup.run(_sim_init(1)[0], 0, 1)
    # SlowRank rides the per-rank gauge into the straggler sentinel
    sent = StragglerSentinel(threshold=4.0)
    sup2 = TrainSupervisor(
        lambda st, i: _sim_step(st), None, dp_degree=4, straggler=sent,
        chaos=TrainChaosPlan([SlowRank(at_step=1, rank=2, factor=8.0,
                                       for_steps=1)]),
        clock=iter(np.arange(1e6)).__next__, sleep=lambda s: None)
    sup2.run(_sim_init(4)[0], 0, 3)
    assert sent.flags_total == 1 and sent.flagged[0][1] == 2
    assert sup2.summary()["straggler_flags_total"] == 1
    assert sup2.summary()["chaos"][0]["fault"] == "SlowRank"


# ---------------------------------------------------------------------------
# sentinels (stock-safe cores + one mesh row)


def test_straggler_sentinel_flags_slow_rank_only():
    class _Alerts:
        def __init__(self):
            self.fired = []

        def fire(self, name, t_ms, severity="warn", **ctx):
            self.fired.append((name, severity, ctx))

    alerts = _Alerts()
    s = StragglerSentinel(threshold=4.0, alerts=alerts)
    assert s.observe(0, [1.0, 1.0, 1.0, 1.0]) == []  # zero false positives
    assert s.observe(1, [1.0, 1.0]) == []  # below min_ranks: stay quiet
    assert s.observe(2, [1.0, 1.0, 1.0, 9.0]) == [3]  # MAD=0 fallback path
    assert s.observe(3, [1.0, 1.01, 0.99, 1.02, 1.0]) == []  # jitter
    assert s.flags_total == 1
    (name, severity, ctx), = alerts.fired
    assert name == "straggler" and ctx["rank"] == 3
    with pytest.raises(ValueError):
        StragglerSentinel(slack=0.5)


def test_sdc_disagreement_host_math():
    agree = jnp.full((4,), 7.5)
    assert float(SDCSentinel.disagreement(agree)) == 0.0
    flipped = agree.at[2].add(1e-3)  # one corrupted rank
    assert float(SDCSentinel.disagreement(flipped)) == 1.0
    assert float(SDCSentinel.disagreement(flipped, tol=1e-2)) == 0.0
    assert float(SDCSentinel.disagreement(agree.at[1].set(jnp.nan))) == 1.0
    with pytest.raises(ValueError):
        SDCSentinel(every=0)


def test_grad_checksum_sums_inexact_leaves_only():
    grads = {"w": jnp.ones((2, 3)), "b": jnp.full((4,), 0.5),
             "step": jnp.int32(9)}
    assert float(grad_checksum(grads)) == 8.0
    assert float(grad_checksum({"i": jnp.int32(3)})) == 0.0


def test_sdc_check_is_rank_uniform_under_shard_map():
    mesh = build_mesh(tp=1, pp=1, sp=1)  # dp=8
    sent = SDCSentinel()

    def prog(x, poison):
        r = lax.axis_index(DP_AXIS)
        g = {"w": x + jnp.where((r == 3) & (poison > 0), 1e-2, 0.0)}
        return sent.check(g)[None]

    run = jax.jit(jax.shard_map(
        prog, mesh=mesh, in_specs=(P("dp"), P()), out_specs=P("dp"),
        check_vma=False))
    clean = np.asarray(run(jnp.ones(8), jnp.int32(0)))
    np.testing.assert_array_equal(clean, np.zeros(8))  # no false positives
    # a one-rank grad flip trips the SAME flag on EVERY rank
    hit = np.asarray(run(jnp.ones(8), jnp.int32(1)))
    np.testing.assert_array_equal(hit, np.ones(8))


# ---------------------------------------------------------------------------
# watch-stage gate coverage


def test_regress_polarity_covers_elastic_headliners():
    from apex_tpu.monitor.regress import classify_metric

    assert classify_metric("reshard_ms") == "lower"
    assert classify_metric("reshard_ms_per_gb") == "lower"
    assert classify_metric("sdc_disagreements_total") == "lower"
    assert classify_metric("straggler_flags_total") == "lower"
    assert classify_metric("retries_total") == "lower"
    # a resume at a new degree is a FEATURE firing, not a regression
    assert classify_metric("elastic_resumes_total") is None
