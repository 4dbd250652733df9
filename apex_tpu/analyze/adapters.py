"""Adapter-pool donation contract — does the LoRA pool RIDE every jit site?

The serve adapter design (``apex_tpu.serve.adapters``) only holds its two
headline promises — zero per-adapter-swap recompiles and zero extra pool
copies — if the pool is threaded through every serve program as a DONATED
input that XLA actually aliases to an output:

* if the pool were closed over instead of passed, every
  ``load_adapter``/``write_adapter`` would change the constant and retrace
  (the recompile leak);
* if it were passed but not donated-and-aliased, every step would copy
  ``adapter_pool_bytes`` of HBM (the donation leak — the same silent
  failure mode :mod:`apex_tpu.analyze.donation` exists to catch for the
  KV pools).

This module promotes that into a contract check on the engine's COMPILED
programs: for each lora-enabled jit site (``chunk_prefill`` / ``decode``
/ ``verify`` when spec-k is on), lower the already-jitted program with
representative arguments — AOT ``lower().compile()``, so the engine's jit
caches and ``compile_counts`` are untouched — and require every leaf of
the KV cache AND the adapter pool (donate argnums 1 and 2) to appear in
the executable's ``input_output_alias`` map.

Pinned by tier-1 tests (``tests/test_analyze.py``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax.numpy as jnp

from apex_tpu.analyze.donation import (DonationError, DonationReport,
                                       check_donation)

__all__ = ["adapter_contract_record", "adapter_donation_report",
           "adapter_jit_sites", "assert_adapter_donated"]


def adapter_jit_sites(engine) -> Dict[str, Tuple[Any, tuple]]:
    """``{site: (jitted_fn, representative_args)}`` for every serve jit
    site the adapter pool rides (argument order mirrors the engine's own
    call sites; shapes come from the engine's mirrors so lowering hits
    the SAME cache entry the live engine compiled)."""
    if getattr(engine, "adapters", None) is None:
        raise ValueError(
            "engine has no adapter pool (ServeConfig.lora_rank == 0) — "
            "nothing for the adapter donation contract to check")
    scfg = engine.serve_cfg
    progs = engine.programs()
    n = scfg.num_slots
    prefill_tokens = jnp.zeros((scfg.prefill_chunk,), jnp.int32)
    sites: Dict[str, Tuple[Any, tuple]] = {
        "chunk_prefill": (progs["chunk_prefill"], (
            engine.params, engine.cache, engine._lora_pool,
            prefill_tokens, jnp.int32(0), jnp.int32(1),
            engine._dev("block_tables")[0], engine._dev("keys")[0],
            engine._dev("adapter_ids")[0])),
        "decode": (progs["decode"], (
            engine.params, engine.cache, engine._lora_pool,
            engine._dev("last_tokens"), engine._dev("seq_lens"),
            engine._dev("active"), engine._dev("block_tables"),
            engine._dev("keys"), engine._dev("adapter_ids"))),
    }
    if progs.get("verify") is not None:
        fed = jnp.zeros((n, scfg.spec_k + 1), jnp.int32)
        n_fed = jnp.zeros((n,), jnp.int32)
        sites["verify"] = (progs["verify"], (
            engine.params, engine.cache, engine._lora_pool,
            fed, engine._dev("seq_lens"), n_fed,
            engine._dev("active"), engine._dev("block_tables"),
            engine._dev("keys"), engine._dev("adapter_ids")))
    return sites


def adapter_donation_report(engine) -> Dict[str, DonationReport]:
    """Per-site :class:`~apex_tpu.analyze.donation.DonationReport` with
    ``expected_leaves`` = leaves(cache) + leaves(pool) — ``ok`` means the
    compiled executable aliases BOTH donated pytrees in full."""
    out: Dict[str, DonationReport] = {}
    for site, (fn, args) in adapter_jit_sites(engine).items():
        out[site] = check_donation(fn, *args, donate_argnums=(1, 2))
    return out


def assert_adapter_donated(engine) -> Dict[str, DonationReport]:
    """:func:`adapter_donation_report`, raising
    :class:`~apex_tpu.analyze.donation.DonationError` naming every site
    where a cache or adapter-pool leaf was silently copied."""
    reports = adapter_donation_report(engine)
    bad: List[str] = []
    for site, rep in reports.items():
        if not rep.ok:
            bad.append(f"{site}: {rep.n_aliased}/{rep.expected_leaves} "
                       f"aliased, {len(rep.unusable)} copied")
    if bad:
        raise DonationError(
            "adapter pool donation not honored — " + "; ".join(bad))
    return reports


def adapter_contract_record(engine) -> Dict[str, Any]:
    """Flat ``json_record`` fields for the analyze-contracts bench record
    (``adapter_donated_copied`` joins the ``donated_copied`` lower-is-
    better polarity family in ``monitor.regress``)."""
    reports = adapter_donation_report(engine)
    copied = sum(len(r.unusable) for r in reports.values())
    aliased = sum(r.n_aliased for r in reports.values())
    expected = sum(r.expected_leaves or 0 for r in reports.values())
    return {"adapter_sites_checked": len(reports),
            "adapter_donated_aliased": aliased,
            "adapter_donated_expected": expected,
            "adapter_donated_copied": copied,
            "adapter_donation_ok": all(r.ok for r in reports.values())}
