"""Monitor tier 4 — performance forensics acceptance gates (ISSUE-17).

All stock-jax-safe (single device, manual clock, SimTransport):

* **attribution identity** — every retired request's queue/prefill/
  transfer/decode/stall components sum to the event-derived e2e exactly
  (stall is the residual and stays >= -tol), INCLUDING chaos-migrated
  requests, and the decomposition is independent of event-log
  concatenation order (merged worker logs replay shared records);
* **explain_regression** — an injected slow component is named in the
  diagnosis, and the component deltas account for the whole e2e move;
* **metering** — one charge per retirement means Σ per-tenant rollups
  == fleet totals to the unit; deterministic across identical runs;
  cardinality overflow folds into ``_overflow`` LOUDLY; unknown
  resources raise; worker cost rates accrue and ride heartbeats;
* satellites: the tier-4 ``monitor.regress`` polarity rows, provenance
  byte-compatibility on ``json_record``, the ``monitor.view``
  attribution table / tenant rollup / ``--baseline`` diagnosis, and the
  ON/OFF cluster config parity (tier-4 off: no keys, same streams).
"""

import json

import pytest

import jax
import jax.numpy as jnp

from apex_tpu.monitor import sink as sink_mod
from apex_tpu.monitor import view
from apex_tpu.monitor.attrib import (
    COMPONENTS,
    DEFAULT_TOL_MS,
    AttributionAccumulator,
    attribute_requests,
    attribution_summary,
    explain_regression,
)
from apex_tpu.monitor.events import EventLog
from apex_tpu.monitor.meter import (
    OVERFLOW_TENANT,
    CostModel,
    Meter,
    modeled_request_flops,
)
from apex_tpu.monitor.regress import classify_metric
from apex_tpu.monitor.slo import SloSpec
from apex_tpu.serve import (
    ClusterChaos,
    ClusterConfig,
    InferenceEngine,
    Request,
    RouterConfig,
    ServeCluster,
    ServeConfig,
)
from apex_tpu.serve.cluster.chaos import KillWorker
from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

CFG = GPTConfig(vocab_size=97, max_seq=64, hidden=32, num_layers=2,
                num_heads=4, dtype=jnp.float32, fused_loss=False)
PARAMS = init_gpt_params(jax.random.PRNGKey(0), CFG)

TREQS = [
    Request("a", [1, 2, 3, 4, 5], max_new_tokens=6, tenant="t0"),
    Request("b", [7, 8, 9], max_new_tokens=8, tenant="t1"),
    Request("c", list(range(20, 42)), max_new_tokens=8, tenant="t0"),
    Request("d", [11, 3, 11, 3, 11, 3, 7], max_new_tokens=9, tenant="t2"),
    Request("e", list(range(60, 73)), max_new_tokens=7, tenant="t1"),
]


def _serve_cfg(**kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return ServeConfig(**kw)


class _ManualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


def _drive(cl, clock=None, tick_ms=5.0, max_steps=20000):
    steps = 0
    while cl.active and steps < max_steps:
        cl.step()
        if clock is not None:
            clock.advance(tick_ms / 1e3)
        steps += 1
    assert steps < max_steps, "cluster failed to drain"


def _run_cluster(chaos=None, n_decode=2, reqs=TREQS, **cfg_kw):
    clock = _ManualClock()
    events = EventLog(keep=True, clock=clock)
    ccfg = ClusterConfig(n_prefill=1, n_decode=n_decode,
                         serve=_serve_cfg(),
                         router=RouterConfig(slo=SloSpec(ttft_ms=600000.0)),
                         **cfg_kw)
    cl = ServeCluster(PARAMS, CFG, ccfg, events=events, chaos=chaos)
    for r in reqs:
        cl.submit(r)
    _drive(cl, clock)
    return cl, events


def _ev(uid, event, t_ms, **kw):
    return {"kind": "event", "uid": uid, "event": event,
            "t_ms": float(t_ms), **kw}


def _check_identity(att, tol=DEFAULT_TOL_MS):
    assert att, "no requests attributed"
    for uid, comp in att.items():
        total = sum(comp[c] for c in COMPONENTS)
        # each of 5 components + e2e round to 3dp independently
        assert total == pytest.approx(comp["e2e_ms"], abs=0.01), uid
        assert comp["stall"] >= -tol, (uid, comp)


# -- attribution: identity, order independence, chaos -----------------------


def test_attribution_synthetic_decomposition():
    """A hand-built lifecycle decomposes into the exact documented
    components, and stall picks up the unexplained residual."""
    recs = [
        _ev("r", "submitted", 0.0, tenant="t0"),
        _ev("r", "admitted", 2.0),
        _ev("r", "prefill_start", 10.0),
        _ev("r", "prefill_end", 30.0),
        _ev("r", "transfer_start", 30.0),
        _ev("r", "transfer_end", 40.0),
        _ev("r", "first_token", 45.0),
        _ev("r", "retired", 100.0),
    ]
    att = attribute_requests(recs)
    comp = att["r"]
    assert comp["queue"] == 10.0       # submitted -> first prefill_start
    assert comp["prefill"] == 20.0
    assert comp["transfer"] == 10.0
    assert comp["decode"] == 55.0      # first_token -> retired, no overlap
    assert comp["stall"] == 5.0        # 40 -> 45 gap
    assert comp["e2e_ms"] == 100.0
    assert comp["tenant"] == "t0"
    assert comp["migrated"] is False
    _check_identity(att)


def test_attribution_transfer_retry_opens_no_second_interval():
    """A retried transfer re-emits ``transfer_start`` with attempt > 1;
    only the first attempt opens an interval (stitch_traces rule)."""
    recs = [
        _ev("r", "submitted", 0.0),
        _ev("r", "prefill_start", 0.0),
        _ev("r", "prefill_end", 10.0),
        _ev("r", "transfer_start", 10.0),
        _ev("r", "transfer_start", 15.0, attempt=2),
        _ev("r", "transfer_end", 20.0),
        _ev("r", "first_token", 20.0),
        _ev("r", "retired", 50.0),
    ]
    comp = attribute_requests(recs)["r"]
    assert comp["transfer"] == 10.0
    assert comp["decode"] == 30.0
    _check_identity({"r": comp})


def test_attribution_order_independent_synthetic():
    base = [
        _ev("x", "submitted", 0.0), _ev("x", "prefill_start", 3.0),
        _ev("x", "prefill_end", 9.0), _ev("x", "first_token", 11.0),
        _ev("x", "retired", 40.0),
        _ev("y", "submitted", 1.0), _ev("y", "prefill_start", 9.0),
        _ev("y", "prefill_end", 14.0), _ev("y", "first_token", 15.0),
        _ev("y", "retired", 33.0),
    ]
    fwd = attribute_requests(base)
    rev = attribute_requests(list(reversed(base)))
    assert fwd == rev


def test_attribution_identity_under_chaos_both_orders():
    """The acceptance pin: a kill-and-migrate run attributes with full
    coverage, the migrated request included, the identity holds for
    every request, and BOTH concatenation orders of the merged log
    yield the identical decomposition."""
    chaos = ClusterChaos([KillWorker(at_step=12, worker="decode0")])
    cl, events = _run_cluster(chaos=chaos)
    st = cl.stats()
    assert st["worker_deaths"] == 1
    assert st["migrations_total"] >= 1

    recs = [r for r in events.records if r.get("kind") == "event"]
    att = attribute_requests(recs)
    _check_identity(att)
    assert set(att) == {r.uid for r in TREQS}
    migrated = [c for c in att.values() if c["migrated"]]
    assert migrated, "no migrated request attributed"
    assert any(c["replayed_tokens"] > 0 for c in migrated)

    # order independence: swap the halves AND fully reverse — a merged
    # worker log has no canonical order, attribution must not care
    half = len(recs) // 2
    swapped = recs[half:] + recs[:half]
    assert attribute_requests(swapped) == att
    assert attribute_requests(list(reversed(recs))) == att

    summ = attribution_summary(recs)
    assert summ["attrib_coverage"] == 1.0
    assert summ["n_retired"] == len(TREQS)

    # the streaming accumulator (what cluster.stats() reports) agrees
    acc = AttributionAccumulator()
    for r in recs:
        acc.tap(r)
    assert acc.summary() == summ
    assert acc.in_flight == 0


def test_cluster_stats_carry_attribution_and_meter():
    cl, _ = _run_cluster()
    st = cl.stats()
    assert st["attrib_coverage"] == 1.0
    assert st["meter_coverage"] == 1.0
    for c in COMPONENTS:
        assert f"{c}_component_ms_p50" in st["attribution"]
    assert st["decode_component_ms_p50"] > 0.0
    assert st["cost_per_token"] > 0.0
    assert st["meter"]["totals"]["requests"] == len(TREQS)
    # heartbeat-advertised worker cost rates (ROADMAP 5c): every decode
    # worker that retired work advertises a positive rate
    rates = st["meter"]["worker_cost_rates"]
    assert any(v > 0.0 for v in rates.values())


def test_tier4_off_no_keys_and_streams_bitwise():
    """``metering=False, attribution=False`` removes the tier-4 surface
    entirely AND the forensics plane never perturbs the work: streams
    bitwise vs the ON run."""
    cl_on, _ = _run_cluster()
    cl_off, _ = _run_cluster(metering=False, attribution=False)
    st = cl_off.stats()
    for k in ("attribution", "attrib_coverage", "meter", "cost_per_token",
              "cost_per_request", "meter_coverage"):
        assert k not in st, k
    assert cl_off.meter is None and cl_off.attrib is None
    assert cl_on.finished == cl_off.finished  # bitwise


# -- explain_regression ------------------------------------------------------


def _lifecycle(uid, *, decode_ms=18.0, transfer=None):
    recs = [
        _ev(uid, "submitted", 0.0),
        _ev(uid, "prefill_start", 5.0),
        _ev(uid, "prefill_end", 10.0),
        _ev(uid, "first_token", 12.0),
    ]
    end = 12.0 + decode_ms
    if transfer is not None:
        a, b = transfer
        recs += [_ev(uid, "transfer_start", a),
                 _ev(uid, "transfer_end", b)]
        end = max(end, b) + decode_ms - min(decode_ms, 0.0)
        end = b + decode_ms  # decode resumes after the hop
    recs.append(_ev(uid, "retired", end))
    return recs


def test_explain_regression_names_injected_decode():
    base = [r for i in range(8) for r in _lifecycle(f"b{i}")]
    slow = [r for i in range(8)
            for r in _lifecycle(f"n{i}", decode_ms=68.0)]
    ex = explain_regression(base, slow)
    assert ex["diagnosis"] == "decode"
    assert ex["top_regressed"][0] == "decode"
    assert ex["delta_ms"] == pytest.approx(50.0, abs=0.01)
    # the component deltas account for ALL of the e2e move
    assert sum(c["delta_ms"] for c in ex["components"]) == pytest.approx(
        ex["delta_ms"], abs=0.01)


def test_explain_regression_names_injected_transfer():
    base = [r for i in range(8) for r in _lifecycle(f"b{i}")]
    slow = [r for i in range(8)
            for r in _lifecycle(f"n{i}", transfer=(12.0, 42.0))]
    ex = explain_regression(base, slow)
    assert ex["diagnosis"] == "transfer"
    dec = [c for c in ex["components"] if c["component"] == "decode"][0]
    assert dec["delta_ms"] == pytest.approx(0.0, abs=0.01)


def test_explain_regression_no_regression_no_diagnosis():
    base = [r for i in range(8) for r in _lifecycle(f"b{i}")]
    ex = explain_regression(base, base)
    assert ex["diagnosis"] is None
    assert ex["delta_ms"] == 0.0


# -- metering ----------------------------------------------------------------


def test_meter_rollup_equals_totals_to_the_unit():
    cl, _ = _run_cluster()
    m = cl.meter
    # RAW ledger identity: totals are literally the field-wise sum
    for key in ("flops", "kv_block_s", "tokens", "requests"):
        raw = sum(led[key] for led in m._tenants.values())
        tot = sum(m._tenants[t][key] for t in m._tenants)
        assert raw == tot
    st = m.stats(completed=cl.completed)
    roll = sum(t["cost_units"] for t in st["tenants"].values())
    # displayed values round per-tenant to 1e-6
    assert roll == pytest.approx(st["totals"]["cost_units"],
                                 abs=len(st["tenants"]) * 1e-6)
    assert sum(t["tokens"] for t in st["tenants"].values()) \
        == st["totals"]["tokens"]
    assert sum(t["requests"] for t in st["tenants"].values()) \
        == st["totals"]["requests"] == cl.completed
    assert st["meter_coverage"] == 1.0
    assert set(st["tenants"]) >= {"t0", "t1", "t2"}


def test_meter_charge_once_under_migration():
    """A migrated request retires exactly once (on the destination), so
    chaos never double-bills: metered requests == completed."""
    chaos = ClusterChaos([KillWorker(at_step=12, worker="decode0")])
    cl, _ = _run_cluster(chaos=chaos)
    assert cl.stats()["migrations_total"] >= 1
    st = cl.meter.stats(completed=cl.completed)
    assert st["totals"]["requests"] == cl.completed == len(TREQS)
    assert st["meter_coverage"] == 1.0


def test_meter_deterministic_across_identical_runs():
    st1 = _run_cluster()[0].meter.stats(completed=len(TREQS))
    st2 = _run_cluster()[0].meter.stats(completed=len(TREQS))
    assert st1 == st2


def test_meter_overflow_is_loud_and_bounded():
    m = Meter(max_tenants=2)
    m.charge("t0", flops=1e9, tokens=1, requests=1)
    m.charge("t1", flops=1e9, tokens=1, requests=1)
    m.charge("t2", flops=1e9, tokens=1, requests=1)  # over the bound
    m.charge("t3", flops=1e9, tokens=1, requests=1)
    st = m.stats()
    assert st["overflow_charges_total"] == 2
    assert OVERFLOW_TENANT in st["tenants"]
    assert st["tenants"][OVERFLOW_TENANT]["requests"] == 2
    # the fold loses per-tenant resolution, never revenue
    assert st["totals"]["requests"] == 4


def test_meter_unknown_resource_raises():
    with pytest.raises(ValueError, match="unknown resource"):
        Meter().charge("t0", watts=9000.0)
    with pytest.raises(ValueError, match="max_tenants"):
        Meter(max_tenants=0)
    with pytest.raises(ValueError, match="meter_max_tenants"):
        ClusterConfig(n_prefill=1, n_decode=1, serve=_serve_cfg(),
                      meter_max_tenants=0).validate()


def test_modeled_flops_shape():
    base = modeled_request_flops(1000000, 2, 32, prompt_len=16,
                                 n_generated=8)
    more = modeled_request_flops(1000000, 2, 32, prompt_len=16,
                                 n_generated=16)
    cached = modeled_request_flops(1000000, 2, 32, prompt_len=16,
                                   n_generated=8, cached_tokens=8)
    assert more > base > cached > 0.0


def test_worker_cost_rate_accrues():
    m = Meter(model=CostModel())
    assert m.worker_cost_rate("w0") == 0.0
    m.charge("t0", worker="w0", t_ms=0.0, flops=1e12, tokens=10,
             requests=1)
    m.charge("t0", worker="w0", t_ms=2000.0, flops=1e12, tokens=10,
             requests=1)
    # 2 cost units over 2 s
    assert m.worker_cost_rate("w0", 2000.0) == pytest.approx(1.0)
    assert m.worker_rates(2000.0) == {"w0": 1.0}


def test_standalone_engine_attribution_and_meter():
    """The single-engine form: attribution histograms + metering without
    a cluster (ServeCluster passes its shared Meter the same way)."""
    m = Meter()
    eng = InferenceEngine(PARAMS, CFG, _serve_cfg(num_slots=4),
                          meter=m, meter_worker="solo")
    reqs = [Request(r.uid, list(r.tokens), max_new_tokens=r.max_new_tokens,
                    tenant=r.tenant) for r in TREQS]
    out = eng.run(reqs)
    assert len(out) == len(TREQS)
    st = eng.stats()
    assert st["attrib_coverage"] == 1.0
    assert st["queue_component_ms_p50"] is not None
    assert st["decode_component_ms_p50"] > 0.0
    assert st["meter_coverage"] == 1.0
    assert st["cost_per_token"] > 0.0
    assert m.stats()["totals"]["requests"] == len(TREQS)
    assert m.worker_cost_rate("solo") > 0.0


# -- satellites: polarity, provenance, view ---------------------------------


def test_regress_polarity_tier4_rows():
    for k in ("decode_component_ms_p50", "stall_component_ms_p99",
              "cost_per_token", "cost_per_request", "drift_score"):
        assert classify_metric(k) == "lower", k
    for k in ("attrib_coverage", "meter_coverage"):
        assert classify_metric(k) == "higher", k


def test_json_record_provenance_byte_compat():
    old = sink_mod._PROVENANCE
    try:
        sink_mod.set_provenance(None)
        line = sink_mod.json_record(metric="m", v=1)
        # byte-for-byte the pre-provenance format when no stamp is set
        assert line == json.dumps(
            {"schema": sink_mod.SCHEMA_VERSION, "metric": "m", "v": 1})
        sink_mod.set_provenance({"git_sha": "abc"})
        rec = json.loads(sink_mod.json_record(metric="m"))
        assert rec["provenance"] == {"git_sha": "abc"}
        # explicit fields win over the process stamp
        rec = json.loads(sink_mod.json_record(metric="m",
                                              provenance={"x": 1}))
        assert rec["provenance"] == {"x": 1}
    finally:
        sink_mod.set_provenance(old)


def test_collect_provenance_keys():
    prov = sink_mod.collect_provenance(extra={"stage": "test"})
    assert "hostname" in prov and "jax_version" in prov
    assert prov["git_sha"]  # tests run inside the repo
    # jax is imported in this process, so the backend is stamped
    assert prov["backend"] == jax.default_backend()
    assert prov["stage"] == "test"


def test_view_attribution_table_tenants_and_baseline(tmp_path, capsys):
    cl, events = _run_cluster()
    path = tmp_path / "events.jsonl"
    with open(path, "w") as f:
        for r in events.records:
            f.write(json.dumps(r) + "\n")
    assert view.main([str(path)]) == 0
    out = capsys.readouterr()
    assert "attribution (coverage 1.0)" in out.err
    for c in COMPONENTS:
        assert c in out.err
    assert "t0" in out.err and "t2" in out.err  # tenant rollup rows
    rec = json.loads(out.out.strip())
    assert rec["attrib_coverage"] == 1.0
    assert rec["tenants"]["t0"]["requests"] == 2
    assert rec["decode_component_ms_p50"] > 0.0
    # --baseline against itself: zero delta, explicit null diagnosis
    assert view.main([str(path), "--baseline", str(path)]) == 0
    out = capsys.readouterr()
    assert "vs baseline: e2e" in out.err
    rec = json.loads(out.out.strip())
    assert rec["explain"]["delta_ms"] == 0.0
    assert rec["explain"]["diagnosis"] is None
