"""Attention as a *logical op* with two Pallas implementations.

The LM hot-spot kernel the framework's models lean on, and the first
multi-variant `@tuned_kernel` (DESIGN.md §15):

* ``flash`` (primary) — online-softmax schedule, grid
  (B*H, Sq/bq, Skv/bkv) with the KV axis innermost/sequential; running
  max/denominator/accumulator live in VMEM scratch across KV steps
  (FlashAttention-2 schedule, adapted to the TPU pipeline: blocks are
  (8,128)-aligned, accumulation in f32 on the MXU).  Tunables: bq, bkv.
* ``blocked`` — single-pass dense schedule, grid (B*H, Sq/bq) with the
  *whole* KV sequence resident per step: one stable softmax over the
  full (bq, skv) logits block, no cross-step carry, no per-KV-step
  re-load of the query block.  Cheaper per element at short KV lengths
  (one softmax pass, less HBM traffic on Q); the f32 logits block
  scales with skv, so long sequences blow VMEM and the static ranking
  swings back to ``flash``.  Tunable: bq.

The variant id is a joint-space axis — `rank_space` scores both
sub-spaces in one streaming pass and the cached/frozen record carries
the winning implementation.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.autotuner import KernelStaticInfo, TunableKernel
from repro.core.search import SearchSpace
from repro.kernels.api import (KernelVariant, cuda_profile, divisors,
                               get_spec, tuned_kernel)
from repro.kernels.common import (block_info, cdiv, default_interpret,
                                  pick_divisor_candidates, require_shape,
                                  require_tiling)
from repro.kernels.ref import attention_ref

__all__ = ["flash_attention_pallas", "blocked_attention_pallas",
           "flash_static_info", "make_tunable_flash"]

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, causal, scale, bq, bkv):
    kv_i = pl.program_id(2)
    q_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)           # (bq, d)
    k = k_ref[0].astype(jnp.float32)           # (bkv, d)
    v = v_ref[0].astype(jnp.float32)           # (bkv, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        rows = q_i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = kv_i * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)

    m_prev = m_ref[...]                         # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                      # (bq, bkv)
    alpha = jnp.exp(m_prev - m_new)             # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv_i == pl.num_programs(2) - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _flash_analysis(p, *, b: int, h: int, sq: int, skv: int, d: int,
                    causal: bool = True, dtype: str = "float32"):
    """Static analysis of one config (scalars) or a lattice ((N,) cols)."""
    bq = np.minimum(np.asarray(p["bq"], dtype=np.int64), sq)
    bkv = np.minimum(np.asarray(p["bkv"], dtype=np.int64), skv)
    steps = (b * h) * cdiv(sq, bq) * cdiv(skv, bkv)
    # causal masking skips ~half the logits -> effective FLOP discount.
    eff = 0.5 if causal and sq == skv else 1.0
    return dict(
        in_blocks=[(bq, d), (bkv, d), (bkv, d)],
        out_blocks=[(bq, d)],
        in_shapes=[(sq, d), (skv, d), (skv, d)],
        out_shapes=[(sq, d)],
        in_dtypes=[dtype] * 3,
        out_dtypes=[dtype],
        flops_per_step=4.0 * bq * bkv * d * eff,   # QK^T + PV
        vpu_per_step=6.0 * bq * bkv * eff,         # mask/max/sum/scale
        trans_per_step=(bq * bkv + bq) * eff,      # exp
        grid_steps=steps,
        scratch_bytes=(bq * 2 + bq * d) * 4,
    )


def _flash_inputs(key, *, b: int, h: int, sq: int, skv: int, d: int,
                  causal: bool = True, dtype: str = "float32"):
    kq, kkey, kv = jax.random.split(key, 3)
    dt = np.dtype(dtype)
    return (jax.random.normal(kq, (b, h, sq, d), dt),
            jax.random.normal(kkey, (b, h, skv, d), dt),
            jax.random.normal(kv, (b, h, skv, d), dt))


# ---------------------------------------------------------------------------
# "blocked" variant: dense single-pass schedule over the full KV length
# ---------------------------------------------------------------------------


def _blocked_kernel(q_ref, k_ref, v_ref, o_ref, *, causal, scale, bq):
    q_i = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0].astype(jnp.float32)            # (skv, d)
    v = v_ref[0].astype(jnp.float32)            # (skv, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        rows = q_i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)

    m = jnp.max(s, axis=1, keepdims=True)       # full row: one stable pass
    p = jnp.exp(s - m)                          # (bq, skv)
    denom = jnp.maximum(p.sum(axis=1, keepdims=True), 1e-30)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0] = (o / denom).astype(o_ref.dtype)


def _blocked_analysis(p, *, b: int, h: int, sq: int, skv: int, d: int,
                      causal: bool = True, dtype: str = "float32"):
    """Static analysis of the dense variant: fewer grid steps and one
    softmax pass (5 vs 6 VPU ops/logit, no running rescale), no causal
    FLOP discount (the dense schedule computes every masked logit), and
    the full (bq, skv) f32 logits block counted as scratch — the term
    that makes long-KV configs VMEM-infeasible, handing the win back to
    ``flash``."""
    bq = np.minimum(np.asarray(p["bq"], dtype=np.int64), sq)
    return dict(
        in_blocks=[(bq, d), (skv, d), (skv, d)],
        out_blocks=[(bq, d)],
        in_shapes=[(sq, d), (skv, d), (skv, d)],
        out_shapes=[(sq, d)],
        in_dtypes=[dtype] * 3,
        out_dtypes=[dtype],
        flops_per_step=4.0 * bq * skv * d,         # QK^T + PV, no discount
        vpu_per_step=5.0 * bq * skv,               # mask/max/sum/div
        trans_per_step=bq * skv + bq,              # exp
        grid_steps=(b * h) * cdiv(sq, bq),
        scratch_bytes=bq * skv * 4,                # f32 logits block
    )


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "interpret"))
def blocked_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = True, *, bq: int = 128,
                             interpret: bool | None = None) -> jax.Array:
    """q, k, v: (B, H, S, D) -> (B, H, S, D); full KV resident per step."""
    if interpret is None:
        interpret = default_interpret()
    b, h, sq, d = q.shape
    skv = k.shape[2]
    require_shape("blocked_attention_pallas", "k", k.shape, (b, h, skv, d))
    require_shape("blocked_attention_pallas", "v", v.shape, (b, h, skv, d))
    bq = min(bq, sq)
    require_tiling("blocked_attention_pallas", {"sq": sq}, {"bq": bq})
    scale = 1.0 / (d ** 0.5)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, skv, d)
    vr = v.reshape(b * h, skv, d)
    kern = functools.partial(_blocked_kernel, causal=causal, scale=scale,
                             bq=bq)
    out = pl.pallas_call(
        kern,
        grid=(b * h, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, skv, d), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, skv, d), lambda g, i: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d)


@tuned_kernel(
    "flash_attention",
    space={"bq": divisors("sq", (8, 16, 32, 64, 128, 256, 512)),
           "bkv": divisors("skv", (8, 16, 32, 64, 128, 256, 512))},
    # causal is positional-or-keyword so the dispatch wrapper keeps the
    # old public signature flash_attention(q, k, v, causal=True, ...)
    signature=lambda q, k, v, causal=True, **_: dict(
        b=q.shape[0], h=q.shape[1], sq=q.shape[2], skv=k.shape[2],
        d=q.shape[3], causal=causal, dtype=str(q.dtype)),
    static_info=_flash_analysis,
    make_inputs=_flash_inputs,
    reference=attention_ref,
    pretune=tuple(dict(b=b, h=h, sq=s, skv=s, d=128, causal=causal,
                       dtype=dt)
                  # short-KV rows are where the dense "blocked" variant
                  # earns its keep; long-KV rows are flash territory
                  for (b, h, s) in [(2, 8, 128), (4, 8, 256),
                                    (2, 4, 1024), (4, 8, 2048),
                                    (1, 8, 4096)]
                  for causal in (True, False)
                  for dt in ("float32", "bfloat16")),
    variants=(KernelVariant(
        variant_id="blocked",
        fn=blocked_attention_pallas,
        space={"bq": divisors("sq", (8, 16, 32, 64, 128, 256, 512))},
        analysis=_blocked_analysis),),
    primary_variant="flash",
    # Not a paper kernel.  Register-heavy (online-softmax accumulators
    # per row): R^u = 64 exceeds Fermi's 63-register architectural cap,
    # so every Fermi launch is infeasible by Eq. 4 — the ranked record
    # carries predicted_s = +inf (serialized as null in JSONL).  One
    # K/V stage pair in shared memory; causal halves the score work.
    cuda=cuda_profile(
        regs=64, shmem_per_block=16384,
        workload=lambda b, h, sq, skv, d, causal=True, **_: dict(
            o_fl=(2.0 if causal else 4.0) * b * h * sq * skv * d,
            o_mem=2.0 * b * h * (sq + skv) * d,
            o_ctrl=1.0 * b * h * sq,
            o_reg=(2.0 if causal else 4.0) * b * h * sq * skv * d)),
)
@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bkv", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True, *, bq: int = 128,
                           bkv: int = 128,
                           interpret: bool | None = None) -> jax.Array:
    """q, k, v: (B, H, S, D) -> (B, H, S, D).  GQA callers broadcast KV."""
    if interpret is None:
        interpret = default_interpret()
    b, h, sq, d = q.shape
    skv = k.shape[2]
    require_shape("flash_attention_pallas", "k", k.shape, (b, h, skv, d))
    require_shape("flash_attention_pallas", "v", v.shape, (b, h, skv, d))
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    require_tiling("flash_attention_pallas", {"sq": sq, "skv": skv},
                   {"bq": bq, "bkv": bkv})
    scale = 1.0 / (d ** 0.5)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, skv, d)
    vr = v.reshape(b * h, skv, d)
    kern = functools.partial(_flash_kernel, causal=causal, scale=scale,
                             bq=bq, bkv=bkv)
    out = pl.pallas_call(
        kern,
        grid=(b * h, sq // bq, skv // bkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d)


def flash_static_info(b: int, h: int, sq: int, skv: int, d: int, dtype,
                      params: Dict, causal: bool = True) -> KernelStaticInfo:
    """Scalar static info for one configuration (wrapper over the
    declared analysis; kept as a stable public helper)."""
    return block_info(**_flash_analysis(params, b=b, h=h, sq=sq, skv=skv,
                                        d=d, causal=causal, dtype=dtype))


def make_tunable_flash(b: int = 2, h: int = 4, s: int = 1024, d: int = 128,
                       causal: bool = True, dtype=jnp.float32,
                       seed: int = 0) -> TunableKernel:
    space = SearchSpace({
        "bq": pick_divisor_candidates(s, (128, 256, 512)),
        "bkv": pick_divisor_candidates(s, (128, 256, 512)),
    })
    return get_spec("flash_attention").tunable(
        b=b, h=h, sq=s, skv=s, d=d, causal=causal,
        dtype=np.dtype(dtype).name, seed=seed,
        space=space, name=f"flash_{b}x{h}x{s}x{d}")
