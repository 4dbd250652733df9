"""apex_tpu.analyze — compiled-program contract checker + repo graph-lint.

Every program analyzer is pinned BOTH ways: a deliberately-broken fixture
(a copied "donated" buffer, a shape-recompiling step, an fp32 dot under a
bf16 policy, a synthetic exposed all-gather, a ``float(tracer)`` sync)
must be caught, and a clean program must pass. The flagship acceptance
rows run the donation checker and the recompile sentinel on the REAL
paths — the GPT train step and the serve chunk-prefill/decode programs —
all stock-jax-safe. Tier B: the repo lint must exit 0 against the
checked-in baseline and exit 1 the moment a new violation is introduced
(round-tripped through a tmp baseline).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import analyze
from apex_tpu.analyze import hlo as hlo_mod
from apex_tpu.analyze import lint
from apex_tpu.analyze.collectives import overlap_assertion
from apex_tpu.comm import accounting

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# analyze.hlo — the shared normalization/parse entry point


def test_as_text_normalizes_str_and_compiled():
    assert hlo_mod.as_text("HloModule x") == "HloModule x"
    compiled = jax.jit(lambda x: x * 2).lower(jnp.ones(3)).compile()
    text = hlo_mod.as_text(compiled)
    assert "HloModule" in text
    with pytest.raises(TypeError):
        hlo_mod.as_text(42)


def test_parse_computations_walks_bare_snippets():
    snippet = (
        "  %a = f32[4] parameter(0)\n"
        "  %b = f32[4] multiply(f32[4] %a, f32[4] %a)\n")
    comps = hlo_mod.parse_computations(snippet)
    assert [op for _, op, _ in comps[""]] == ["parameter", "multiply"]


def test_accounting_imports_the_shared_parser():
    """Satellite: ONE HLO normalization/walker — accounting's parser IS
    analyze.hlo's (identity, not a copy), and collective_report accepts
    both text and compiled objects through the same as_text."""
    assert accounting._parse_computations is hlo_mod.parse_computations
    compiled = jax.jit(lambda x: x + 1).lower(jnp.ones(3)).compile()
    rep_obj = accounting.collective_report(compiled)
    rep_txt = accounting.collective_report(compiled.as_text())
    assert rep_obj.counts == rep_txt.counts


def test_input_output_alias_header_parse():
    header = ("HloModule jit_step, is_scheduled=true, input_output_alias="
              "{ {0}: (0, {}, may-alias), {1}: (2, {}, must-alias) }, "
              "entry_computation_layout={(f32[4])->f32[4]}\n")
    aliases = hlo_mod.input_output_aliases(header)
    assert [(p, k) for _, p, _, k in aliases] == \
        [(0, "may-alias"), (2, "must-alias")]
    assert hlo_mod.input_output_aliases("HloModule bare\n") == []


# ---------------------------------------------------------------------------
# donation checker


def test_donation_clean_step_aliased():
    def step(p, x):
        return p + x, (p * x).sum()

    rep = analyze.assert_donated(step, jnp.ones((4, 4)), jnp.ones((4, 4)),
                                 donate_argnums=(0,))
    assert rep.ok and rep.n_aliased == 1 and rep.expected_leaves == 1
    assert rep.as_record()["donation_ok"] is True


def test_donation_copied_buffer_flagged():
    """THE seeded defect: the donated buffer's only same-shaped output has
    a different dtype, so XLA silently copies instead of aliasing."""
    def bad(p, x):
        return (p + x).astype(jnp.bfloat16), (p * x).sum()

    rep = analyze.check_donation(bad, jnp.ones((4, 4)), jnp.ones((4, 4)),
                                 donate_argnums=(0,))
    assert not rep.ok and rep.n_aliased == 0
    with pytest.raises(analyze.DonationError):
        analyze.assert_donated(bad, jnp.ones((4, 4)), jnp.ones((4, 4)),
                               donate_argnums=(0,))


def test_donation_pytree_counts_all_leaves():
    def step(state, x):
        return {"w": state["w"] + x, "b": state["b"] * 2.0}, x.sum()

    state = {"w": jnp.ones((3, 3)), "b": jnp.ones((3,))}
    rep = analyze.assert_donated(step, state, jnp.ones((3, 3)),
                                 donate_argnums=(0,))
    assert rep.expected_leaves == 2 and rep.n_aliased >= 2


# ---------------------------------------------------------------------------
# recompile sentinel


def test_recompile_guard_steady_state():
    step = jax.jit(lambda x: x + 1)
    with analyze.recompile_guard(step) as g:   # warmup contract
        for _ in range(5):
            step(jnp.ones(4))
    if g.supported:
        assert g.growth() == {"<lambda>": 1}


def test_recompile_guard_catches_shape_recompiling_step():
    """THE seeded defect: a step re-jitted per input shape."""
    step = jax.jit(lambda x: x * 2)
    step(jnp.ones(4))  # warm
    guard = analyze.recompile_guard({"step": step}, budget=0)
    with pytest.raises(analyze.RecompileError, match="step: \\+2"):
        with guard:
            step(jnp.ones(5))
            step(jnp.ones(6))


def test_recompile_guard_budget_allows_declared_compiles():
    step = jax.jit(lambda x: x - 1)
    with analyze.recompile_guard({"step": step}, budget=2):
        step(jnp.ones(3))
        step(jnp.ones(8))   # 2 compiles, budget 2: fine


def test_recompile_guard_disambiguates_name_collisions():
    """Two bare callables sharing __name__ (every step is named 'step')
    must BOTH be guarded, not silently collapsed to one."""
    a, b = jax.jit(lambda x: x + 1), jax.jit(lambda x: x * 2)
    with analyze.recompile_guard(a, b) as g:
        a(jnp.ones(2))
        b(jnp.ones(2))
    assert len(g.programs) == 2
    if g.supported:
        assert sorted(g.growth().values()) == [1, 1]


def test_jit_cache_size_shapes():
    assert analyze.jit_cache_size(None) == 0
    assert analyze.jit_cache_size(lambda x: x) is None  # not jitted
    f = jax.jit(lambda x: x)
    f(jnp.ones(2))
    n = analyze.jit_cache_size(f)
    assert n is None or n == 1
    counts = analyze.compile_counts({"f": f, "g": None})
    assert counts["g"] == 0


# ---------------------------------------------------------------------------
# dtype-leak detector


_W_BF16 = jnp.ones((4, 4), jnp.bfloat16)
_X_BF16 = jnp.ones((2, 4), jnp.bfloat16)


def test_dtype_leak_fp32_dot_under_bf16_policy():
    """THE seeded defect: a dot promoted to f32 under a bf16 policy."""
    def leaky(x, w):
        return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))

    rep = analyze.dtype_leak_report(leaky, _X_BF16, _W_BF16,
                                    policy=jnp.bfloat16)
    assert rep.fp32_dots == 1 and not rep.ok
    with pytest.raises(analyze.DtypeLeakError, match="fp32 dot"):
        analyze.assert_no_dtype_leaks(leaky, _X_BF16, _W_BF16,
                                      policy=jnp.bfloat16)


def test_dtype_leak_clean_bf16_dot():
    rep = analyze.assert_no_dtype_leaks(jnp.dot, _X_BF16, _W_BF16,
                                        policy=jnp.bfloat16)
    assert rep.ok and rep.total_dots == 1 and rep.fp32_dots == 0


def test_dtype_leak_convert_churn_roundtrip():
    def churny(x, w):
        h = x.astype(jnp.float32).astype(jnp.bfloat16)  # f32 round trip
        return jnp.dot(h, w)

    rep = analyze.dtype_leak_report(churny, _X_BF16, _W_BF16,
                                    policy=jnp.bfloat16)
    assert rep.convert_churn_ops == 1 and rep.fp32_dots == 0
    with pytest.raises(analyze.DtypeLeakError, match="round-trip"):
        analyze.assert_no_dtype_leaks(churny, _X_BF16, _W_BF16,
                                      policy=jnp.bfloat16)
    # a single direction-changing cast is NOT churn
    def single(x, w):
        return jnp.dot(x.astype(jnp.float32).astype(jnp.bfloat16)
                       if False else x, w)
    assert analyze.dtype_leak_report(
        single, _X_BF16, _W_BF16, policy=jnp.bfloat16).convert_churn_ops == 0


def test_dtype_leak_f32_accumulate_is_not_a_leak():
    """bf16 operands accumulating into f32 (preferred_element_type — the
    TPU-native pattern) must NOT flag; only fp32 OPERANDS (the fp32 MXU
    path) are the leak. An explicit allowance admits deliberate fp32
    sites (attention-stability math)."""
    def accum(x, w):
        return jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    rep = analyze.assert_no_dtype_leaks(accum, _X_BF16, _W_BF16,
                                        policy=jnp.bfloat16)
    assert rep.fp32_dots == 0 and rep.fp32_accum_dots == 1

    def leaky(x, w):
        return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    rep2 = analyze.assert_no_dtype_leaks(leaky, _X_BF16, _W_BF16,
                                         policy=jnp.bfloat16,
                                         allow_fp32_dots=1)
    assert rep2.fp32_dots == 1  # admitted by the declared allowance


def test_dtype_leak_walks_scan_bodies():
    def scanned(x, w):
        def body(h, _):
            h = jnp.dot(h.astype(jnp.float32), w.astype(jnp.float32))
            return h.astype(jnp.bfloat16), ()
        h, _ = jax.lax.scan(body, x, None, length=3)
        return h

    rep = analyze.dtype_leak_report(scanned, _X_BF16, _W_BF16,
                                    policy=jnp.bfloat16)
    assert rep.fp32_dots == 1  # found inside the scan body


def test_policy_resolution_rules():
    from apex_tpu import amp
    from apex_tpu.transformer.testing import GPTConfig

    assert analyze.resolve_policy_dtype(jnp.bfloat16) == jnp.bfloat16
    assert analyze.resolve_policy_dtype(
        amp.get_policy("O2")) == jnp.bfloat16
    assert analyze.resolve_policy_dtype(amp.get_policy("O0")) is None
    cfg = GPTConfig(vocab_size=97, max_seq=64, hidden=32, num_layers=2,
                    num_heads=4, dtype=jnp.bfloat16)
    assert analyze.resolve_policy_dtype(cfg) == jnp.bfloat16

    # O0 (no declared low precision): fp32 dots are NOT leaks
    def fp32_dot(x, w):
        return jnp.dot(x, w)
    rep = analyze.dtype_leak_report(
        fp32_dot, jnp.ones((2, 4)), jnp.ones((4, 4)),
        policy=amp.get_policy("O0"))
    assert rep.ok and rep.fp32_dots == 0


def test_fsdp_policy_dtype_declaration():
    """The fsdp wiring: FSDP.policy_dtype declares the widest
    low-precision FLOAT leaf dtype — int8 codebooks/bool masks never
    masquerade as the compute dtype (that would disarm the leak gate)."""
    from apex_tpu.fsdp.core import FSDP, LeafMeta

    f = FSDP()
    meta = {"w": LeafMeta((4, 4), "bfloat16"),
            "codes": LeafMeta((4,), "int8"),
            "b": LeafMeta((4,), "float32")}
    assert f.policy_dtype(meta) == jnp.dtype(jnp.bfloat16)
    assert f.policy_dtype({"w": LeafMeta((2,), "float32")}) == \
        jnp.dtype(jnp.float32)
    assert f.policy_dtype({"codes": LeafMeta((4,), "int8")}) is None
    assert analyze.resolve_policy_dtype(
        f.policy_dtype(meta)) == jnp.dtype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# exposed-collective checker

_EXPOSED_AG = """\
HloModule synthetic, is_scheduled=true

ENTRY %main (p0: f32[1024]) -> f32[4096] {
  %p0 = f32[1024] parameter(0)
  %ag = f32[4096] all-gather(f32[1024] %p0), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %r = f32[4096] add(f32[4096] %ag, f32[4096] %ag)
}
"""

_HIDDEN_AG = """\
HloModule synthetic, is_scheduled=true

ENTRY %main (p0: f32[1024], a: f32[8,8], b: f32[8,8]) -> f32[4096] {
  %p0 = f32[1024] parameter(0)
  %a = f32[8,8] parameter(1)
  %b = f32[8,8] parameter(2)
  %ag = f32[4096] all-gather(f32[1024] %p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %d = f32[8,8] dot(f32[8,8] %a, f32[8,8] %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %r = f32[4096] add(f32[4096] %ag, f32[4096] %ag)
}
"""


def test_exposed_synthetic_all_gather_caught():
    """THE seeded defect: an all-gather with nothing to hide behind."""
    rep = analyze.exposed_report(_EXPOSED_AG)
    # f32[4096] result = 16384B, ring model: b*(W-1)/W over W=4
    assert rep.exposed_wire_bytes == pytest.approx(12288.0)
    assert rep.hidden_wire_bytes == 0.0 and rep.collectives == 1
    with pytest.raises(analyze.ExposedCollectiveError, match="all-gather"):
        analyze.assert_no_exposed(_EXPOSED_AG)
    # ... but an explicit budget admits it
    rep2 = analyze.assert_no_exposed(_EXPOSED_AG, budget_bytes=16384)
    assert rep2.as_record()["exposed_bytes"] == 12288


def test_exposed_hidden_behind_independent_dot():
    """Clean program: a def-use-independent dot in the same computation —
    a latency-hiding scheduler can overlap the gather."""
    rep = analyze.assert_no_exposed(_HIDDEN_AG)
    assert rep.hidden == 1 and rep.exposed_wire_bytes == 0.0
    assert rep.hidden_fraction == 1.0


def test_exposed_report_on_collective_free_program():
    compiled = jax.jit(lambda x: x * 2 + 1).lower(jnp.ones(8)).compile()
    rep = analyze.assert_no_exposed(compiled)
    assert rep.collectives == 0 and rep.hidden_fraction == 1.0


def test_overlap_assertion_floor():
    with pytest.raises(analyze.ExposedCollectiveError, match="under-hidden"):
        overlap_assertion(
            "  %cp = f32[64] collective-permute(f32[64] %x), "
            "source_target_pairs={{0,1}}\n", min_hidden_fraction=0.5)


# ---------------------------------------------------------------------------
# host-sync detector


def test_host_sync_float_tracer_caught():
    """THE seeded defect: float() on a traced value inside the step."""
    def step(x):
        return float(jnp.sum(x))

    rep = analyze.host_sync_report(step, jnp.ones(3))
    assert rep.implicit_syncs == 1 and rep.host_syncs == 1
    assert "float" in (rep.implicit_kind or "") \
        or "concretization" in (rep.implicit_kind or "")
    with pytest.raises(analyze.HostSyncError, match="implicit sync"):
        analyze.assert_no_host_sync(step, jnp.ones(3))


def test_host_sync_explicit_apis_counted():
    def step(x):
        jax.device_get(x)
        y = jax.block_until_ready(x * 2)
        return y + 1

    rep = analyze.host_sync_report(step, jnp.ones(3))
    assert rep.device_gets == 1 and rep.block_until_readys == 1
    assert rep.host_syncs == 2 and not rep.ok
    assert rep.as_record()["host_syncs"] == 2


def test_host_sync_clean_step():
    def step(p, x):
        g = jax.grad(lambda p: jnp.sum((x @ p) ** 2))(p)
        return p - 0.1 * g

    rep = analyze.assert_no_host_sync(step, jnp.ones((4, 2)),
                                      jnp.ones((3, 4)))
    assert rep.ok and rep.host_syncs == 0


def test_host_sync_method_form_block_until_ready_caught():
    """The METHOD form (`y.block_until_ready()`) syncs through an
    attribute tracers don't have — counted as a sync, not an analyzer
    crash; unrelated AttributeErrors still surface as bugs."""
    def step(x):
        return (x * 2).block_until_ready()

    rep = analyze.host_sync_report(step, jnp.ones(3))
    assert rep.implicit_syncs == 1
    assert rep.implicit_kind == "sync method on tracer"

    def buggy(x):
        return x.no_such_attribute_anywhere()
    with pytest.raises(AttributeError):
        analyze.host_sync_report(buggy, jnp.ones(3))


def test_host_sync_tracer_bool_branch_caught():
    def step(x):
        if jnp.sum(x) > 0:    # data-dependent Python branch
            return x
        return -x

    rep = analyze.host_sync_report(step, jnp.ones(3))
    assert rep.implicit_syncs == 1
    assert rep.implicit_kind == "bool(tracer)"


# ---------------------------------------------------------------------------
# Tier B: repo graph-lint

_BAD_SOURCE = '''\
import functools

import jax
import jax.numpy as jnp


@jax.jit
def branchy(x):
    if jnp.sum(x) > 0:
        return jnp.array(x)
    return x


@functools.partial(jax.jit, static_argnums=(1,))
def train_step(p, n):
    return p


def helper(a, acc=[]):
    try:
        return a
    except Exception:
        return None
'''

_CLEAN_SOURCE = '''\
import functools

import jax
import jax.numpy as jnp


@jax.jit
def branchless(x):
    return jnp.where(jnp.sum(x) > 0, jnp.asarray(x), x)


@functools.partial(jax.jit, donate_argnums=(0,))
def train_step(p, g):
    return p - 0.1 * g


def helper(a, acc=None):
    try:
        return a
    except Exception:  # fixture: deliberately swallowed for the test
        return None
'''


def _lint_src(tmp_path, source, name="mod.py"):
    f = tmp_path / name
    f.write_text(source)
    return lint.lint_file(str(f), root=str(tmp_path))


def test_lint_catches_all_seeded_rules(tmp_path):
    found = {v.rule for v in _lint_src(tmp_path, _BAD_SOURCE)}
    assert found == {"tracer-branch", "jnp-array-on-tracer",
                     "missing-donate", "mutable-default-arg",
                     "bare-except"}


def test_lint_clean_file_passes(tmp_path):
    assert _lint_src(tmp_path, _CLEAN_SOURCE) == []


def test_lint_jit_call_form_missing_donate(tmp_path):
    src = ("import jax\n\n"
           "def decode_step(c, t):\n    return c\n\n"
           "prog = jax.jit(decode_step)\n"
           "good = jax.jit(decode_step, donate_argnums=(0,))\n")
    rules = [v.rule for v in _lint_src(tmp_path, src)]
    assert rules == ["missing-donate"]


def test_lint_comment_justifies_bare_except(tmp_path):
    src = ("def f():\n"
           "    try:\n        return 1\n"
           "    # best-effort: telemetry must never kill the step\n"
           "    except Exception:\n        return None\n")
    assert _lint_src(tmp_path, src) == []


def test_lint_baseline_roundtrip(tmp_path):
    """Acceptance: add violation -> exit 1; bless it -> exit 0; add a NEW
    one -> exit 1 again (multiset: a second copy of a blessed pattern
    still flags)."""
    mod = tmp_path / "mod.py"
    mod.write_text(_BAD_SOURCE)
    base = tmp_path / "baseline.json"
    argv = [str(mod), "--baseline", str(base), "--root", str(tmp_path)]
    assert lint.main(argv) == 1                       # no baseline yet
    assert lint.main(argv + ["--write-baseline"]) == 0
    assert lint.main(argv) == 0                       # blessed
    mod.write_text(_BAD_SOURCE +
                   "\n\ndef another(b, xs=[]):\n    return b\n")
    assert lint.main(argv) == 1                       # new violation fails
    data = json.loads(base.read_text())
    assert data["schema"] == 1 and len(data["violations"]) == 5


def test_lint_baseline_is_line_drift_proof(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(_BAD_SOURCE)
    base = tmp_path / "baseline.json"
    argv = [str(mod), "--baseline", str(base), "--root", str(tmp_path)]
    lint.main(argv + ["--write-baseline"])
    # unrelated edit shifts every line; the baseline still covers
    mod.write_text("# a new header comment\n\n" + _BAD_SOURCE)
    assert lint.main(argv) == 0


def test_repo_lint_gate_green():
    """THE tier-1 wiring: the repo lints clean against the checked-in
    baseline. A new anti-pattern anywhere under apex_tpu/ fails here."""
    rc = lint.main([os.path.join(ROOT, "apex_tpu"),
                    "--baseline",
                    os.path.join(ROOT, "tests", "lint_baseline.json"),
                    "--root", ROOT])
    assert rc == 0


# ---------------------------------------------------------------------------
# regress polarity (satellite: analyzer record fields classified)


def test_regress_polarity_for_analyzer_fields():
    from apex_tpu.monitor.regress import classify_metric

    for key in ("exposed_bytes", "convert_churn_ops", "host_syncs",
                "lint_violations", "fp32_dots", "donated_copied"):
        assert classify_metric(key) == "lower", key
    assert classify_metric("hidden_fraction") == "higher"
    assert classify_metric("hidden_bytes") == "higher"


def test_regress_gates_analyzer_record():
    from apex_tpu.monitor.regress import compare_records

    base = {"exposed_bytes": 0, "host_syncs": 0, "lint_violations": 0,
            "convert_churn_ops": 0}
    rep = compare_records(base, dict(base, host_syncs=2), tol=0.15)
    assert not rep["ok"]
    assert rep["regressions"][0]["key"] == "host_syncs"
    assert compare_records(base, dict(base), tol=0.15)["ok"]


# ---------------------------------------------------------------------------
# flagship acceptance: the REAL paths, tier-1


def test_flagship_gpt_train_step_donation_and_recompile():
    """Acceptance (stock-safe): a GPT train step over the flagship layer
    stack (the serve ``gpt_prefill`` forward, tp-optional — the same
    transformer the mesh ``gpt_loss`` runs) donates its params, the
    compiled executable ALIASES them, and N steps reuse ONE compilation."""
    from apex_tpu.serve.decode import gpt_prefill

    cfg, params, kv, cache = _serve_fixture()
    toks = jnp.zeros((16,), jnp.int32).at[:9].set(
        jnp.arange(1, 10, dtype=jnp.int32))
    block_row = jnp.arange(2, dtype=jnp.int32)

    def train_step(p, toks, target):
        def loss_fn(p):
            _, logits = gpt_prefill(p, toks, jnp.int32(9), cache,
                                    block_row, cfg, kv)
            return -jax.nn.log_softmax(logits)[target]

        loss, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree_util.tree_map(
            lambda a, b: a - 0.01 * b, p, g), loss

    n_leaves = len(jax.tree_util.tree_leaves(params))
    rep = analyze.assert_donated(train_step, params, toks, jnp.int32(7),
                                 donate_argnums=(0,))
    assert rep.n_aliased >= n_leaves

    step = jax.jit(train_step, donate_argnums=(0,))
    p = jax.tree_util.tree_map(jnp.copy, params)
    with analyze.recompile_guard(step) as g:
        for _ in range(3):
            p, loss = step(p, toks, jnp.int32(7))
    assert np.isfinite(float(loss))
    if g.supported:
        assert g.growth() == {"train_step": 1}


def test_flagship_gpt_mesh_loss_step_donation_and_recompile():
    """Acceptance: the step the benchmark's cells time
    (``apex_tpu.train.train_step_fn``, on a tiny config, parameters and
    state placed by ``param_specs()`` as ``perfbench/kinds/train.py``
    places them, Adam's moments beside them): donated params and optimizer
    state aliased, one compilation over three steps."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel.mesh import build_mesh
    from apex_tpu.train import train_step_fn
    from apex_tpu.transformer.testing import GPTConfig

    cfg = GPTConfig(vocab_size=96, max_seq=32, hidden=32, num_layers=2,
                    num_heads=4, dtype=jnp.float32)
    mesh = build_mesh(tp=1, pp=1, sp=1, dp=1, devices=jax.devices()[:1])
    step, opt = train_step_fn(cfg, mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             cfg.param_specs())
    params = jax.device_put(cfg.init_params(jax.random.PRNGKey(0)),
                            shardings)
    state = opt.init(params)
    state = jax.device_put(state, state._replace(
        count=NamedSharding(mesh, P()), mu=shardings, nu=shardings))
    tok = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 96),
        NamedSharding(mesh, P("dp")))

    # Lowerings are counted, not ``recompile_guard``'s cache entries: jit's
    # fast path keys on the sharding objects, the step hands its state back
    # under normalised specs (P(None, "tp", None) as P(None, "tp"); P() on
    # one device), and the second call adds an entry that compiles nothing.
    lowered = []

    def on_event(event, secs, **kw):
        if event.endswith("jaxpr_to_mlir_module_duration"):
            lowered.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for _ in range(3):
            params, state, loss = step(params, state, tok, tok)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert np.isfinite(float(loss))
    assert len(lowered) == 1, lowered

    n_donated = len(jax.tree.leaves((params, state)))
    rep = analyze.assert_donated(step, params, state, tok, tok,
                                 donate_argnums=(0, 1))
    assert rep.n_aliased >= n_donated


def _serve_fixture(dtype=jnp.float32):
    from apex_tpu.serve import KVCacheConfig, init_kv_cache
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

    cfg = GPTConfig(vocab_size=97, max_seq=64, hidden=32, num_layers=2,
                    num_heads=4, dtype=dtype, fused_loss=False)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    kv = KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                       num_blocks=8, block_size=8, dtype=dtype)
    return cfg, params, kv, init_kv_cache(kv)


def _decode_program(dtype=jnp.float32):
    """The serve decode step (model and pools in ``dtype``) with the
    arguments of one step over three slots: ``(decode, (cache, ...))``."""
    from apex_tpu.serve.decode import gpt_decode_step

    cfg, params, kv, cache = _serve_fixture(dtype)
    n = 3
    toks = jnp.zeros((n,), jnp.int32)
    lens = jnp.array([4, 2, 0], jnp.int32)
    active = jnp.array([True, True, False])
    bt = jnp.arange(n * 2, dtype=jnp.int32).reshape(n, 2)

    def decode(cache, toks, lens, active, bt):
        return gpt_decode_step(params, toks, lens, active, cache, bt,
                               cfg, kv, tp_axis=None, use_pallas=False)

    return decode, (cache, toks, lens, active, bt)


def test_flagship_serve_decode_step_donation():
    """Acceptance: the serve decode step's donated KV pools are aliased —
    a silently-copied pool would double serve HBM."""
    decode, args = _decode_program()
    n_pool_leaves = len(jax.tree_util.tree_leaves(args[0]))
    rep = analyze.assert_donated(decode, *args, donate_argnums=(0,))
    assert rep.n_aliased >= n_pool_leaves
    # ... and the step itself is host-sync-free
    sync = analyze.assert_no_host_sync(decode, *args)
    assert sync.host_syncs == 0


def test_flagship_serve_decode_dtype_profile():
    """Acceptance: the bf16 decode program keeps two fp32 dots (attention's
    scores and its weighted values, for stability: the accepted level) and
    no convert round trips."""
    decode, args = _decode_program(jnp.bfloat16)
    leak = analyze.dtype_leak_report(decode, *args, policy=jnp.bfloat16)
    assert leak.fp32_dots == 2, leak
    assert leak.convert_churn_ops == 0, leak


def test_flagship_serve_decode_no_host_sync():
    """Acceptance: nothing reachable from the decode step in the deployed
    dtype (bf16 model and pools) waits for the device."""
    decode, args = _decode_program(jnp.bfloat16)
    assert analyze.host_sync_report(decode, *args).host_syncs == 0


def test_flagship_serve_chunk_prefill_donation():
    from apex_tpu.serve.decode import gpt_prefill_chunk

    cfg, params, kv, cache = _serve_fixture()
    toks = jnp.zeros((8,), jnp.int32)

    def chunk(cache, toks, start, n_valid, block_row):
        return gpt_prefill_chunk(params, toks, start, n_valid, cache,
                                 block_row, cfg, kv, tp_axis=None,
                                 use_pallas=False)

    n_pool_leaves = len(jax.tree_util.tree_leaves(cache))
    rep = analyze.assert_donated(
        chunk, cache, toks, jnp.int32(0), jnp.int32(5),
        jnp.arange(2, dtype=jnp.int32), donate_argnums=(0,))
    assert rep.n_aliased >= n_pool_leaves


def test_flagship_engine_steady_state_no_new_compiles():
    """Acceptance: a warmed engine serves a fresh mixed-length workload
    with ZERO new compilations — the recompile sentinel wraps the
    engine's own programs (the generalized compile-count gate)."""
    from apex_tpu.serve import (
        InferenceEngine, Request, SamplingConfig, ServeConfig,
    )

    cfg, params, _, _ = _serve_fixture()
    eng = InferenceEngine(params, cfg, ServeConfig(
        num_slots=3, block_size=8, prefill_chunk=8,
        sampling=SamplingConfig()))
    eng.run([Request("warm1", [1, 2, 3], max_new_tokens=2),
             Request("warm2", list(range(12)), max_new_tokens=2)])
    with analyze.recompile_guard(eng.programs(), budget=0):
        out = eng.run([Request("a", [5, 6], max_new_tokens=3),
                       Request("b", list(range(17)), max_new_tokens=2)])
    assert len(out["a"]) == 3 and len(out["b"]) == 2
    counts = eng.compile_counts()
    if counts["decode"] is not None:
        assert counts == {"chunk_prefill": 1, "decode": 1, "verify": 0,
                          "cow_copy": 0}


# ---------------------------------------------------------------------------
# analyze.adapters — the serve LoRA pool donation contract (PR-16)


def _lora_engine(spec_k=0):
    from apex_tpu.serve import (
        InferenceEngine, Request, SamplingConfig, ServeConfig,
        make_adapter_weights,
    )

    cfg, params, _, _ = _serve_fixture()
    eng = InferenceEngine(params, cfg, ServeConfig(
        num_slots=3, block_size=8, prefill_chunk=8, spec_k=spec_k,
        sampling=SamplingConfig(), lora_rank=4, max_adapters=2))
    eng.load_adapter("t0", make_adapter_weights(
        cfg, 4, jax.random.PRNGKey(11)), scale=0.5)
    eng.run([Request("warm-base", [1, 2, 3], max_new_tokens=2),
             Request("warm-t0", list(range(12)), max_new_tokens=2,
                     adapter="t0")])
    return cfg, eng


def test_flagship_adapter_pool_rides_every_jit_site_donated():
    """Acceptance: the AdapterPool is a donated, ALIASED input of every
    serve jit site — a copied pool would double adapter HBM per step."""
    cfg, eng = _lora_engine()
    reports = analyze.assert_adapter_donated(eng)
    assert set(reports) == {"chunk_prefill", "decode"}
    cache_leaves = len(jax.tree_util.tree_leaves(eng.cache))
    pool_leaves = len(jax.tree_util.tree_leaves(eng._lora_pool))
    for site, rep in reports.items():
        assert rep.expected_leaves == cache_leaves + pool_leaves, site
        assert rep.n_aliased >= rep.expected_leaves, site
        assert not rep.unusable, site
    rec = analyze.adapter_contract_record(eng)
    assert rec["adapter_donation_ok"] is True
    assert rec["adapter_donated_copied"] == 0
    assert rec["adapter_sites_checked"] == 2


def test_flagship_adapter_sites_include_verify_under_spec_k():
    cfg, eng = _lora_engine(spec_k=2)
    reports = analyze.adapter_donation_report(eng)
    assert set(reports) == {"chunk_prefill", "decode", "verify"}
    assert all(r.ok for r in reports.values())


def test_adapter_contract_refuses_lora_free_engine():
    from apex_tpu.serve import (
        InferenceEngine, SamplingConfig, ServeConfig,
    )

    cfg, params, _, _ = _serve_fixture()
    eng = InferenceEngine(params, cfg, ServeConfig(
        num_slots=3, block_size=8, prefill_chunk=8,
        sampling=SamplingConfig()))
    with pytest.raises(ValueError, match="lora_rank"):
        analyze.adapter_jit_sites(eng)


def test_flagship_adapter_swap_zero_new_compiles():
    """Acceptance: loading/unloading adapters on a warm engine and
    serving an adapter-bound workload compiles NOTHING new — residency
    is pool data, not a program constant (the aid=0 base path and the
    adapter path share one executable per site), and the AOT donation
    check itself leaves the jit caches untouched."""
    from apex_tpu.serve import Request, make_adapter_weights

    cfg, eng = _lora_engine()
    analyze.assert_adapter_donated(eng)  # AOT: must not pollute caches
    with analyze.recompile_guard(eng.programs(), budget=0):
        eng.unload_adapter("t0")
        eng.load_adapter("t1", make_adapter_weights(
            cfg, 4, jax.random.PRNGKey(12)), scale=0.5)
        out = eng.run([Request("a", [5, 6], max_new_tokens=3,
                               adapter="t1"),
                       Request("b", list(range(17)), max_new_tokens=2)])
    assert len(out["a"]) == 3 and len(out["b"]) == 2
    counts = eng.compile_counts()
    if counts["decode"] is not None:
        assert counts == {"chunk_prefill": 1, "decode": 1, "verify": 0,
                          "cow_copy": 0}
