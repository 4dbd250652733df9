"""Dtype-leak detector — fp32 matmuls and convert churn under an amp policy.

An amp policy (bf16 model dtype, fp8 casts, O1 per-op autocast) is a
claim about the PROGRAM: the hot GEMMs run in the low-precision dtype and
values do not ping-pong through f32 on the way. Nothing enforced that
claim — one missing ``.astype`` upstream of a ``dot`` silently runs the
matmul in fp32 at half the TPU's throughput, and a cast placed inside the
wrong scope round-trips every activation f32→bf16→f32. This detector
walks the jaxpr (all sub-jaxprs: ``scan`` bodies, ``pjit`` calls,
``custom_vjp`` wrappers, remat) and reports:

* ``fp32_dots`` — ``dot_general``/``conv_general_dilated`` equations
  whose OPERANDS are f32/f64 while the declared policy dtype is
  low-precision (the "fp32 dot under a bf16 policy" leak — the matmul
  rides the fp32 MXU path), with source sites. Low-precision operands
  accumulating into f32 (``preferred_element_type`` — the TPU-native
  pattern) are NOT leaks; they count separately as ``fp32_accum_dots``;
* ``convert_churn_ops`` — ``convert_element_type`` equations whose input
  was itself produced by a convert in the OPPOSITE direction (an
  f32↔policy-dtype round trip on one edge: pure overhead).

The policy can be declared as a dtype, an
:class:`~apex_tpu.config.PrecisionConfig` (the amp opt-level presets), or
anything with a ``.dtype`` field (``GPTConfig``, FSDP leaf meta) —
:func:`resolve_policy_dtype` is the one resolution rule, shared with the
amp/fsdp wiring.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

__all__ = ["DtypeLeakError", "DtypeLeakReport", "assert_no_dtype_leaks",
           "dtype_leak_report", "resolve_policy_dtype"]

_LOW_PRECISION = ("bfloat16", "float16", "float8_e4m3", "float8_e4m3fn",
                  "float8_e5m2", "float8_e4m3fnuz", "float8_e5m2fnuz",
                  "float8_e4m3b11fnuz")
# the policy lattice: a dot is ON-policy when its operands sit at or below
# the declared dtype's rung. fp8 forward (e4m3) and gradient (e5m2) casts
# share the bottom rung — an fp8 policy accepts both (the e4m3/e5m2 split
# is the recipe, not a leak).
_HALF = ("bfloat16", "float16")
_FP8 = tuple(d for d in _LOW_PRECISION if d.startswith("float8"))
_WIDE = ("float32", "float64")
_HOT_PRIMS = ("dot_general", "conv_general_dilated")


class DtypeLeakError(AssertionError):
    """The compiled-program dtype story contradicts the declared policy."""


def resolve_policy_dtype(policy) -> Optional[Any]:
    """One rule for "what dtype did the caller declare": a dtype-like
    passes through; a ``PrecisionConfig`` resolves to its model-cast or
    per-op compute dtype (``None`` for O0 — full precision, nothing to
    leak); an object with ``.dtype`` (``GPTConfig``, FSDP leaf meta)
    contributes that."""
    if policy is None:
        return None
    if hasattr(policy, "cast_model_type") or hasattr(policy, "compute_dtype"):
        # an amp PrecisionConfig: the declaration rule is amp's, not ours
        from apex_tpu.amp.frontend import policy_compute_dtype
        return policy_compute_dtype(policy)
    if hasattr(policy, "dtype") and not isinstance(policy, jnp.dtype):
        return jnp.dtype(policy.dtype)
    return jnp.dtype(policy)


def _subjaxprs(eqn) -> Iterator[Any]:
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if isinstance(v, jex_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jex_core.Jaxpr):
                yield v


def _walk(jaxpr) -> Iterator[Tuple[Any, Any]]:
    """Yield ``(jaxpr, eqn)`` over the whole nest (scan/while bodies,
    pjit/remat calls, custom-vjp wrappers)."""
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for sub in _subjaxprs(eqn):
            yield from _walk(sub)


def _site(eqn) -> str:
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:  # source info is best-effort decoration only
        return ""


def _out_dtype(eqn) -> Optional[Any]:
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            return aval.dtype
    return None


def _in_dtype(eqn) -> Optional[Any]:
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            return aval.dtype
    return None


def _has_wide_operand(eqn) -> bool:
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None and dt.name in _WIDE:
            return True
    return False


@dataclasses.dataclass
class DtypeLeakReport:
    """Jaxpr-level precision evidence for one traced program."""

    policy_dtype: Optional[str]
    fp32_dots: int = 0
    fp32_dot_sites: Tuple[str, ...] = ()
    fp32_accum_dots: int = 0  # low-precision operands, f32 accumulate: ok
    # dots one lattice rung ABOVE an fp8 policy (bf16/f16 operands):
    # informational, never raise — fp8 recipes legitimately keep some
    # sites half (norm-adjacent math) but the count should not creep
    off_policy_half_dots: int = 0
    convert_ops: int = 0
    convert_churn_ops: int = 0
    churn_sites: Tuple[str, ...] = ()
    total_dots: int = 0

    @property
    def ok(self) -> bool:
        return self.fp32_dots == 0 and self.convert_churn_ops == 0

    def as_record(self) -> dict:
        return {"fp32_dots": self.fp32_dots,
                "fp32_accum_dots": self.fp32_accum_dots,
                "off_policy_half_dots": self.off_policy_half_dots,
                "convert_churn_ops": self.convert_churn_ops,
                "convert_ops": self.convert_ops,
                "total_dots": self.total_dots,
                "dtype_ok": self.ok}

    def __repr__(self):
        return (f"DtypeLeakReport(policy={self.policy_dtype}, "
                f"fp32_dots={self.fp32_dots}/{self.total_dots}, "
                f"convert_churn={self.convert_churn_ops}"
                f"/{self.convert_ops} converts)")


def dtype_leak_report(fn, *args, policy, **kwargs) -> DtypeLeakReport:
    """Trace ``fn(*args, **kwargs)`` (or accept a ``ClosedJaxpr``) and
    report dtype leaks against the declared ``policy`` (see
    :func:`resolve_policy_dtype`)."""
    policy_dt = resolve_policy_dtype(policy)
    if isinstance(fn, jex_core.ClosedJaxpr):
        closed = fn
    else:
        closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    rep = DtypeLeakReport(
        policy_dtype=str(policy_dt) if policy_dt is not None else None)
    low_policy = policy_dt is not None and policy_dt.name in _LOW_PRECISION
    fp32_sites: List[str] = []
    churn_sites: List[str] = []

    # producer maps are per-jaxpr (vars are scoped); group the walk
    by_jaxpr: dict = {}
    for jpr, eqn in _walk(closed.jaxpr):
        by_jaxpr.setdefault(id(jpr), []).append(eqn)

    for eqns in by_jaxpr.values():
        producer = {}
        for eqn in eqns:
            for v in eqn.outvars:
                producer[v] = eqn
        for eqn in eqns:
            name = eqn.primitive.name
            if name in _HOT_PRIMS:
                rep.total_dots += 1
                out_dt = _out_dtype(eqn)
                if low_policy and _has_wide_operand(eqn):
                    # f32 OPERANDS: the matmul computes on the fp32 MXU
                    # path — the leak
                    rep.fp32_dots += 1
                    fp32_sites.append(_site(eqn))
                else:
                    if low_policy and out_dt is not None \
                            and out_dt.name in _WIDE:
                        # low-precision operands accumulating into f32
                        # (preferred_element_type): TPU-native, not a leak
                        rep.fp32_accum_dots += 1
                    if low_policy and policy_dt.name in _FP8 and any(
                            getattr(getattr(v, "aval", None), "dtype",
                                    None) is not None
                            and v.aval.dtype.name in _HALF
                            for v in eqn.invars):
                        # one lattice rung above an fp8 policy: counted,
                        # never raised (see _HALF note above)
                        rep.off_policy_half_dots += 1
            elif name == "convert_element_type":
                src, dst = _in_dtype(eqn), _out_dtype(eqn)
                if src is None or dst is None:
                    continue
                pair = {src.name, dst.name}
                if not (pair & set(_WIDE) and pair & set(_LOW_PRECISION)):
                    continue  # only f32↔low-precision edges are policed
                rep.convert_ops += 1
                prev = producer.get(eqn.invars[0])
                if prev is not None and \
                        prev.primitive.name == "convert_element_type":
                    psrc, pdst = _in_dtype(prev), _out_dtype(prev)
                    if psrc is not None and pdst is not None \
                            and psrc.name == dst.name \
                            and pdst.name == src.name:
                        rep.convert_churn_ops += 1  # A→B→A round trip
                        churn_sites.append(_site(eqn))
    rep.fp32_dot_sites = tuple(fp32_sites)
    rep.churn_sites = tuple(churn_sites)
    return rep


def assert_no_dtype_leaks(fn, *args, policy, allow_fp32_dots: int = 0,
                          allow_churn: int = 0, **kwargs) -> DtypeLeakReport:
    """:func:`dtype_leak_report`, raising :class:`DtypeLeakError` on
    fp32-operand dots/convs beyond ``allow_fp32_dots`` (for the rare
    deliberately-fp32 site, e.g. attention-stability math) or convert
    churn beyond ``allow_churn`` round-trips. f32-ACCUMULATED
    low-precision dots never raise (``fp32_accum_dots`` is
    informational)."""
    rep = dtype_leak_report(fn, *args, policy=policy, **kwargs)
    problems = []
    if rep.fp32_dots > allow_fp32_dots:
        sites = "; ".join(s for s in rep.fp32_dot_sites if s) or "(no src)"
        problems.append(
            f"{rep.fp32_dots} fp32 dot/conv under the "
            f"{rep.policy_dtype} policy (allowed {allow_fp32_dots}) "
            f"at {sites}")
    if rep.convert_churn_ops > allow_churn:
        sites = "; ".join(s for s in rep.churn_sites if s) or "(no src)"
        problems.append(
            f"{rep.convert_churn_ops} f32↔{rep.policy_dtype} convert "
            f"round-trips (allowed {allow_churn}) at {sites}")
    if problems:
        raise DtypeLeakError("dtype policy violated: " +
                             "; ".join(problems))
    return rep
