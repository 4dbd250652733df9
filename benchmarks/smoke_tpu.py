"""Kernel-against-reference rows for every compiled Pallas kernel.

The CPU test suite validates the kernels in Pallas interpret mode; these
rows execute the COMPILED kernels on the chip and compare each with its
reference. ``chip_smoke.py`` imports the rows of the kernels on the main
path (``MAIN_PATH``: flash causal fwd+bwd, fused LM-head+CE at vocab 50304,
LayerNorm at 768, paged attention fp and int8, the fused decode block, the
Adam tail, the gated delta rule); this script runs all of them:

    python benchmarks/smoke_tpu.py [--out smoke.json]     # on the chip
    python benchmarks/smoke_tpu.py --cpu-rehearsal        # tiny, interpret

Every row forces its kernel (``use_pallas=True``), so a shape the kernel
refuses raises instead of quietly taking the reference. Inputs are bf16 —
the model dtype the kernels exist for — against a float32 reference traced
under matmul precision "highest": the default lowers fp32 dots to one bf16
MXU pass, which would make the *reference* bf16-accurate. The error metric
is max |a-b| normalised by the reference's max |b| — scale-relative, stable
at near-zero entries. A row passes when ``0 < err <= tol``: a kernel and a
separately computed reference cannot agree bitwise, so an error of exactly
0.0 means the row compared the reference with itself. The exit code is 1
when any row fails; anything a row raises propagates.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Callable, Dict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

KEY = jax.random.PRNGKey(0)


def nerr(got, want) -> float:
    """max-abs error normalised by the reference tensor's scale, worst leaf."""
    return max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
              / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-12))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def _highest(fn, *args):
    """``fn`` jitted under matmul precision "highest" (true-fp32 dots)."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


def _qkv(small: bool):
    shape = (2, 4, 256 if small else 1024, 64)
    return tuple(jax.random.normal(jax.random.fold_in(KEY, i), shape,
                                   jnp.bfloat16) for i in range(3))


# ---------------------------------------------------------------------------
# main-path rows


def flash_causal(small: bool) -> float:
    from apex_tpu.ops.attention import attention_reference, flash_attention

    q, k, v = _qkv(small)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=True).astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss(lambda *a, **kw: flash_attention(
        *a, use_pallas=True, **kw)), argnums=(0, 1, 2)))(q, k, v)
    gr = _highest(jax.grad(loss(attention_reference), argnums=(0, 1, 2)),
                  q, k, v)
    return nerr(g, gr)


def lm_head(small: bool) -> float:
    """Fused LM-head + CE at the GPT-2 vocabulary, fwd + both grads."""
    from apex_tpu.ops.lm_head_loss import lm_head_loss

    n, hid, vocab = (256, 128, 1024) if small else (2048, 768, 50304)
    x = (jax.random.normal(KEY, (n, hid)) * 0.5).astype(jnp.bfloat16)
    w = (jax.random.normal(jax.random.fold_in(KEY, 5), (vocab, hid))
         * 0.02).astype(jnp.bfloat16)
    t = jax.random.randint(jax.random.fold_in(KEY, 6), (n,), 0, vocab)

    def loss(x, w):
        return jnp.mean(lm_head_loss(x, w, t, use_pallas=True))

    def loss_ref(x, w):
        lg = x.astype(jnp.float32) @ w.astype(jnp.float32).T
        return -jnp.mean(jax.nn.log_softmax(lg)[jnp.arange(n), t])

    got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(x, w)
    want = _highest(jax.value_and_grad(loss_ref, argnums=(0, 1)), x, w)
    return nerr(got, want)


def layer_norm_row(hidden: int) -> Callable[[bool], float]:
    def row(small: bool) -> float:
        from apex_tpu.ops.layer_norm import layer_norm, layer_norm_reference

        x = jax.random.normal(KEY, (256, hidden)).astype(jnp.bfloat16)
        w = jax.random.normal(jax.random.fold_in(KEY, 3), (hidden,)) * 0.1 + 1
        b = jax.random.normal(jax.random.fold_in(KEY, 4), (hidden,)) * 0.1

        def loss(ln):
            return lambda x, w, b: jnp.sum(
                ln(x, w, b).astype(jnp.float32) ** 2)

        g = jax.jit(jax.grad(loss(lambda *a: layer_norm(
            *a, use_pallas=True)), argnums=(0, 1, 2)))(x, w, b)
        gr = _highest(jax.grad(loss(lambda x, w, b: layer_norm_reference(
            x.astype(jnp.float32), w, b)), argnums=(0, 1, 2)), x, w, b)
        return nerr(g, gr)

    return row


def _paged_fixture(small: bool, quantized: bool):
    """A pool written through ``paged_write`` (so an int8 pool holds real
    codes and scales), block tables that scatter each slot's blocks across
    the pool, and context lengths from empty to a full table."""
    from apex_tpu.serve import KVCacheConfig, init_kv_cache
    from apex_tpu.serve.kv_cache import paged_write

    heads, d, bs = (4, 64, 16) if small else (12, 64, 16)
    slots, mb = (3, 4) if small else (4, 64)
    kv = KVCacheConfig(num_layers=1, num_heads=heads, head_dim=d,
                       num_blocks=slots * mb, block_size=bs,
                       dtype=jnp.bfloat16, quantized=quantized)
    cl = {k: v[0] for k, v in init_kv_cache(kv).items()}
    perm = np.random.default_rng(0).permutation(slots * mb)
    bt = jnp.asarray(perm.reshape(slots, mb), jnp.int32)
    ctx = mb * bs
    lens = jnp.asarray(([0, 5, ctx] if small else [0, 5, 600, ctx]),
                       jnp.int32)
    for i in range(slots):
        kn, vn = (jax.random.normal(jax.random.fold_in(KEY, 10 * i + j),
                                    (heads, ctx, d), jnp.bfloat16)
                  for j in range(2))
        cl = paged_write(cl, kv, kn, vn, jnp.broadcast_to(bt[i], (ctx, mb)),
                         jnp.arange(ctx), jnp.ones((ctx,), bool))
    return kv, cl, bt, lens


def paged_row(quantized: bool) -> Callable[[bool], float]:
    def row(small: bool) -> float:
        from apex_tpu.serve.decode import (
            paged_attention,
            paged_attention_reference,
        )

        kv, cl, bt, lens = _paged_fixture(small, quantized)
        q = jax.random.normal(jax.random.fold_in(KEY, 99),
                              (bt.shape[0], kv.num_heads, kv.head_dim),
                              jnp.bfloat16)
        got = jax.jit(lambda q, cl: paged_attention(
            q, cl, kv, bt, lens, use_pallas=True))(q, cl)
        want = _highest(lambda q, cl: paged_attention_reference(
            q.astype(jnp.float32), cl,
            dataclasses.replace(kv, dtype=jnp.float32), bt, lens), q, cl)
        # slot 0 has no context: the kernel emits zeros there, the
        # reference a finite junk row the engine masks — compare live slots
        return nerr(got[1:], want[1:])

    return row


def fused_decode(small: bool) -> float:
    """One fused decode layer block against the per-op layer body run on
    the references (paged gather + softmax, XLA LayerNorm) in fp32."""
    from apex_tpu.serve.decode import paged_layer_stack
    from apex_tpu.serve.megakernel import fused_layer_decode
    from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

    kv, cl, bt, lens = _paged_fixture(small, quantized=False)
    hidden = kv.num_heads * kv.head_dim
    cfg = GPTConfig(vocab_size=512, max_seq=int(lens[-1]) + 1, hidden=hidden,
                    num_layers=1, num_heads=kv.num_heads,
                    dtype=jnp.bfloat16, ln_pallas=False)
    layers = init_gpt_params(jax.random.fold_in(KEY, 7), cfg)["layers"]
    # the last slot's table is full: one more token would not fit
    lens = jnp.minimum(lens, int(lens[-1]) - 1)
    x = jax.random.normal(jax.random.fold_in(KEY, 8),
                          (bt.shape[0], hidden)).astype(jnp.bfloat16)

    got = jax.jit(lambda x, lp, cl: fused_layer_decode(
        x, lp, cl, cfg, kv, bt, lens)[0])(
            x, jax.tree.map(lambda a: a[0], layers), cl)

    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    kv32 = dataclasses.replace(kv, dtype=jnp.float32)

    def ref(x, layers, cl):
        n = x.shape[0]
        out, _ = paged_layer_stack(
            x[:, None], layers, lens, jnp.ones((n,), jnp.int32),
            jnp.ones((n,), bool), {k: v[None] for k, v in cl.items()}, bt,
            cfg32, kv32, use_pallas=False)
        return out[:, 0]

    want = _highest(ref, f32(x), f32(layers), f32(cl))
    return nerr(got, want)


def adam_tail(small: bool) -> float:
    """The fused Adam tail against the same update in numpy float64 (an
    elementwise fp32 kernel CAN agree bitwise with an fp32 reference; it
    cannot with a float64 one)."""
    from apex_tpu.ops.fused_update import fused_adam_tail

    n = 70_001 if small else 768 * 3072 + 5  # unaligned: the padding path
    g, m, p = (jax.random.normal(jax.random.fold_in(KEY, i), (n,))
               for i in range(3))
    v = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 3), (n,))) * 1e-2
    c1, c2 = 0.1, 0.001
    b1, b2, eps, wd = 0.9, 0.999, 1e-8, 0.01
    got = jax.jit(lambda g, m, v, p: fused_adam_tail(
        g, m, v, p, jnp.float32(c1), jnp.float32(c2), betas=(b1, b2),
        eps=eps, weight_decay=wd, use_pallas=True))(g, m, v, p)
    g64, m64, v64, p64 = (np.asarray(a, np.float64) for a in (g, m, v, p))
    m_new = b1 * m64 + (1 - b1) * g64
    v_new = b2 * v64 + (1 - b2) * g64 * g64
    u = (m_new / c1) / (np.sqrt(v_new / c2) + eps) + wd * p64
    return max(float(np.max(np.abs(np.asarray(a, np.float64) - b))
                     / np.max(np.abs(b)))
               for a, b in zip(got, (u, m_new, v_new)))


# ---------------------------------------------------------------------------
# rows off the main path


def flash_dropout_determinism(small: bool) -> float:
    from apex_tpu.ops.attention import flash_attention

    q, k, v = _qkv(small)

    def run(seed):
        return jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, use_pallas=True, dropout_rate=0.1,
            dropout_seed=jnp.int32(seed)))(q, k, v)

    a, b, c = run(7), run(7), run(8)
    if float(jnp.max(jnp.abs(a - c))) <= 1e-3:
        return float("nan")  # a different seed must drop differently
    return float(jnp.max(jnp.abs(a - b)))  # same seed: bitwise equal


def flash_dropout_global_offsets(small: bool) -> float:
    """The ring-SP dropout contract on one chip: a dense kernel call must
    equal the same computation CHUNKED with global position offsets (the
    [seed, q_off, k_off] SMEM operand) — non-causal so every chunk is the
    plain kernel, merged by the ring's lse rule."""
    from apex_tpu.ops._pallas_util import compiled_backend
    from apex_tpu.ops.attention import _fa_fwd, flash_attention

    q, k, v = _qkv(small)
    b, h, s, d = q.shape
    seed, rate = jnp.int32(4242), 0.2
    dense = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=False, use_pallas=True, dropout_rate=rate,
        dropout_seed=seed))(q, k, v)

    def chunked(q, k, v):
        half = s // 2
        q3 = q.reshape(b * h, s, d)
        outs = []
        for k_off in (0, half):
            k3 = k[:, :, k_off:k_off + half].reshape(b * h, half, d)
            v3 = v[:, :, k_off:k_off + half].reshape(b * h, half, d)
            sv = jnp.stack([seed, jnp.int32(0), jnp.int32(k_off)])
            o3, lse3 = _fa_fwd(q3, k3, v3, 1.0 / d ** 0.5, False, 128, 128,
                               interpret=not compiled_backend(),
                               dropout_rate=rate, seed=sv)
            outs.append((o3, lse3[..., 0]))
        (o1, l1), (o2, l2) = outs
        lse = jnp.logaddexp(l1, l2)
        o = (o1.astype(jnp.float32) * jnp.exp(l1 - lse)[..., None]
             + o2.astype(jnp.float32) * jnp.exp(l2 - lse)[..., None])
        return o.reshape(b, h, s, d)

    # identical masks by construction; only bf16 merge rounding
    return nerr(jax.jit(chunked)(q, k, v), dense)


def flash_bias(small: bool) -> float:
    """T5 relative-position-bias contract: batch-shared (h, sq, sk) additive
    logit bias, grads for q/k/v AND the bias (the batch-reducing dbias
    kernel)."""
    from apex_tpu.ops.attention import attention_reference, flash_attention

    q, k, v = _qkv(small)
    h, s = q.shape[1], q.shape[2]
    bias = jax.random.normal(jax.random.fold_in(KEY, 9), (h, s, s))

    def loss(attn):
        return lambda q, k, v, bias: jnp.sum(
            attn(q, k, v, causal=True, bias=bias).astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss(lambda *a, **kw: flash_attention(
        *a, use_pallas=True, **kw)), argnums=(0, 1, 2, 3)))(q, k, v, bias)
    gr = _highest(jax.grad(loss(attention_reference), argnums=(0, 1, 2, 3)),
                  q, k, v, bias)
    return nerr(g, gr)


def varlen(small: bool) -> float:
    from apex_tpu.ops.attention_varlen import (
        attention_varlen_reference,
        flash_attention_varlen,
    )

    q, k, v = _qkv(small)
    b, s = q.shape[0], q.shape[2]
    seg = jnp.where(jnp.arange(s)[None, :] < s // 2, 0, 1) * jnp.ones(
        (b, 1), jnp.int32)
    seg = seg.at[:, -64:].set(-1)  # pad tail exercises the skip path

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, seg, causal=True).astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss(lambda *a, **kw: flash_attention_varlen(
        *a, use_pallas=True, **kw)), argnums=(0, 1, 2)))(q, k, v)
    gr = _highest(jax.grad(loss(attention_varlen_reference),
                           argnums=(0, 1, 2)), q, k, v)
    return nerr(g, gr)


def softmax_xent(small: bool) -> float:
    """Plain-XLA ops (no Pallas): scaled causal softmax and label-smoothed
    cross entropy against their textbook forms."""
    from apex_tpu.ops.softmax import scaled_upper_triang_masked_softmax
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss

    xx = jax.random.normal(KEY, (4, 8, 256, 256), jnp.float32)
    y = jax.jit(lambda a: scaled_upper_triang_masked_softmax(a, 1.0))(xx)
    ref = jax.nn.softmax(
        jnp.where(jnp.tril(jnp.ones((256, 256), bool)), xx, -1e9), -1)
    e1 = float(jnp.max(jnp.abs(y - ref)))
    lg = jax.random.normal(KEY, (512, 1000), jnp.float32)
    tt = jax.random.randint(jax.random.fold_in(KEY, 7), (512,), 0, 1000)
    l1 = jax.jit(lambda lg: jnp.mean(softmax_cross_entropy_loss(
        lg, tt, smoothing=0.1)))(lg)
    onehot = jax.nn.one_hot(tt, 1000) * 0.9 + 0.1 / 1000
    l2 = -jnp.mean(jnp.sum(jax.nn.log_softmax(lg) * onehot, -1))
    return max(e1, float(jnp.abs(l1 - l2)))


def delta_rule(small: bool) -> float:
    """The gated delta rule's kernels (``ops.delta_rule._kernels``, called
    as such so that no dispatch can hand the row XLA's form) at the hybrid
    cell's head sizes (d_k 96, d_v 192, chunk 64), forward and the five
    gradients, where the write strength passes 1 and where the decay is
    strong. float32 q, k, v, so that what is compared is the float32 the
    configuration states for the core and not bfloat16's rounding of ``o``.
    Two comparisons, each the worst entry's error over a limit, at most 1:

    * against the recurrence token by token, with the CPU tests' limits
      (``tests/test_delta_rule.py``: forward 2e-6 + 2e-5 |want|, a gradient
      2e-5 x its largest entry + 1e-4 |want|) at those tests' 128 tokens.
      Most of what is read here is the distance between the chip's
      recurrence and any chunked form: XLA's reads 0.72, and 1.0 over 256;
    * against XLA's chunked form (the same arithmetic, so the tight one) at a
      quarter of those limits, over 640 tokens: five pairs of chunks, the state
      carried from one to the next."""
    from apex_tpu.ops import delta_rule as dr

    b, h, dk, dv, chunk = (1, 2, 16, 32, 16) if small else (1, 2, 96, 192, 64)
    kernels = lambda *a: dr._kernels(*a, chunk, interpret=small)
    worst = 0.0
    for shift_beta, shift_g in ((2.0, -3.0), (0.0, 4.0)):    # beta_above_1, strong_decay
        for t, reference, tighter in (
                (2 * chunk, dr.gated_delta_rule_reference, 1.0),
                (10 * chunk, lambda *a: dr._chunked(*a, chunk), 0.25)):
            ks = jax.random.split(jax.random.fold_in(KEY, 29), 6)
            q = dr.l2_normalize(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
            k = dr.l2_normalize(jax.random.normal(ks[1], (b, t, h, dk)))
            v = jax.random.normal(ks[2], (b, t, h, dv))
            beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h)) + shift_beta)
            g = -jax.nn.softplus(jax.random.normal(ks[4], (b, t, h)) + shift_g)
            w = jax.random.normal(ks[5], (b, t, h, dv))
            out = lambda fn: lambda *a: (fn(*a), jax.grad(
                lambda *b: jnp.sum(w * fn(*b)), argnums=range(5))(*a))
            assert dr._kernels_take(q, k, v, chunk)
            got, got_g = jax.jit(out(kernels))(q, k, v, g, beta)
            want, want_g = _highest(out(reference), q, k, v, g, beta)
            over = lambda a, b, atol, rtol: float(jnp.max(
                jnp.abs(a - b) / (tighter * (atol + rtol * jnp.abs(b)))))
            worst = max([worst, over(got, want, 2e-6, 2e-5)] + [
                over(a, b, 2e-5 * float(jnp.max(jnp.abs(b))), 1e-4)
                for a, b in zip(got_g, want_g)])
    return worst


# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Row:
    fn: Callable[[bool], float]
    tol: float
    # a kernel against a separately computed reference cannot agree
    # bitwise; rows that assert equality (dropout determinism) or compare
    # XLA with XLA set this False
    zero_is_fallback: bool = True


ROWS: Dict[str, Row] = {
    "flash_attention_fwd_bwd_causal": Row(flash_causal, 2e-2),
    "fused_lm_head_cross_entropy": Row(lm_head, 2e-2),
    "pallas_layer_norm_h768": Row(layer_norm_row(768), 2e-2),
    "paged_attention_fp": Row(paged_row(False), 2e-2),
    "paged_attention_int8": Row(paged_row(True), 2e-2),
    "fused_decode_block": Row(fused_decode, 3e-2),
    "fused_adam_tail": Row(adam_tail, 1e-5),
    # the worst error over its limit, not an error: at most 1
    "gated_delta_rule_fwd_bwd": Row(delta_rule, 1.0),
    "flash_attention_inkernel_dropout": Row(flash_dropout_determinism, 0.0,
                                            zero_is_fallback=False),
    "flash_attention_dropout_global_offsets": Row(
        flash_dropout_global_offsets, 2e-2),
    "flash_attention_additive_bias": Row(flash_bias, 2e-2),
    "flash_attention_varlen_block_skip": Row(varlen, 2e-2),
    "pallas_layer_norm_h16k": Row(layer_norm_row(16384), 2e-2),
    "scaled_softmax_and_xentropy": Row(softmax_xent, 1e-4,
                                       zero_is_fallback=False),
}

# the kernels the GPT-2 and hybrid train steps and the paged serve engine run
MAIN_PATH = ("flash_attention_fwd_bwd_causal", "fused_lm_head_cross_entropy",
             "pallas_layer_norm_h768", "paged_attention_fp",
             "paged_attention_int8", "fused_decode_block", "fused_adam_tail",
             "gated_delta_rule_fwd_bwd")


def run_row(name: str, small: bool = False) -> dict:
    """Run one row; ``ok`` iff the error is finite, within tolerance and —
    for a kernel-against-reference row — not exactly 0.0."""
    row = ROWS[name]
    t0 = time.perf_counter()
    err = float(row.fn(small))
    ok = bool(np.isfinite(err) and err <= row.tol)
    out = {"kernel": name, "ok": ok, "max_err": err, "tol": row.tol,
           "seconds": round(time.perf_counter() - t0, 2)}
    if row.zero_is_fallback and err == 0.0:
        out["ok"] = False
        out["error"] = ("err == 0.0: a kernel and a separate reference "
                        "cannot agree bitwise; the row compared the "
                        "reference with itself")
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny shapes through the Pallas interpreter: "
                         "rehearses the harness, proves nothing about the "
                         "compiled kernels")
    args = ap.parse_args()
    if not args.cpu_rehearsal and jax.default_backend() != "tpu":
        raise SystemExit(
            f"smoke_tpu.py checks the compiled kernels; the default backend "
            f"is {jax.default_backend()!r} (--cpu-rehearsal runs the "
            f"harness through the interpreter instead)")
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    rows = []
    for name in ROWS:
        rows.append(run_row(name, small=args.cpu_rehearsal))
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    res = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())},
           "cpu_rehearsal": args.cpu_rehearsal, "kernels": rows,
           "total_seconds": round(time.perf_counter() - t0, 1)}
    text = json.dumps(res, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0 if all(r["ok"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
