"""Gated-MLP up-projection as a logical op with two Pallas variants.

``mlp_matmul(x, w_gate, w_up, act=...)`` computes
``act(x @ w_gate) * (x @ w_up)`` — the SwiGLU/GeGLU front half every
gated transformer MLP runs, and the second multi-variant
`@tuned_kernel` (DESIGN.md §15):

* ``fused`` (primary) — one kernel, grid (M/bm, F/bn, D/bk) with the
  contraction axis innermost/sequential and TWO f32 accumulator tiles
  (gate and up) carried across D steps; the activation and gating
  multiply run once at the flush.  The x block is loaded once per
  (i, j, k) step and feeds both dots — half the activation traffic of
  running two matmuls — but the doubled accumulator scratch and the
  third operand block raise VMEM pressure per step.
* ``stream`` — no contraction tiling at all: grid (M/bm, F/bn), each
  step pulls a whole (bm, D) activation panel plus (D, bn) weight
  panels and emits the gated tile in one shot.  No accumulator
  scratch, no k-loop, and the output is written exactly once — but
  the whole-D panels make the per-step working set scale with D, so
  VMEM feasibility (and the weight re-read amortization that bigger
  row blocks would buy) collapses as the contraction grows.
* ``split`` — two plain blocked matmuls (gate pass, up pass) and a jnp
  elementwise combine.  Each pass carries one accumulator, so larger
  block shapes stay VMEM-feasible; the price is re-reading x for the
  second pass and a third output-sized elementwise sweep.

The static ranking arbitrates per (shape, dtype, target): stream wins
while D-panels fit (fewer grid steps, single output flush, zero
scratch), fused takes over once the contraction must be tiled, split
is the VMEM-lean fallback.  ``stream``'s sub-space has no ``bk`` axis
— the joint lattice pins that foreign axis, and dispatch filters it
from the launch (DESIGN.md §15).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.api import KernelVariant, divisors, tuned_kernel
from repro.kernels.common import (cdiv, default_interpret, require_shape,
                                  require_tiling)
from repro.kernels.ref import _MLP_ACTS, mlp_matmul_ref

__all__ = ["mlp_matmul_fused_pallas", "mlp_matmul_split_pallas"]

_SIZES = (8, 16, 32, 64, 128, 256, 512, 1024)


def _fused_kernel(x_ref, g_ref, u_ref, o_ref, gacc_ref, uacc_ref, *, act):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        gacc_ref[...] = jnp.zeros_like(gacc_ref)
        uacc_ref[...] = jnp.zeros_like(uacc_ref)

    xb = x_ref[...]
    gacc_ref[...] += jnp.dot(xb, g_ref[...],
                             preferred_element_type=jnp.float32)
    uacc_ref[...] += jnp.dot(xb, u_ref[...],
                             preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        a = _MLP_ACTS[act]
        o_ref[...] = (a(gacc_ref[...]) * uacc_ref[...]).astype(o_ref.dtype)


def _fused_analysis(p, *, m: int, d: int, f: int, act: str = "silu",
                    dtype: str = "float32"):
    """Static analysis of one config (scalars) or a lattice ((N,) cols)."""
    bm = np.minimum(np.asarray(p["bm"], dtype=np.int64), m)
    bn = np.minimum(np.asarray(p["bn"], dtype=np.int64), f)
    bk = np.minimum(np.asarray(p["bk"], dtype=np.int64), d)
    steps = cdiv(m, bm) * cdiv(f, bn) * cdiv(d, bk)
    return dict(
        in_blocks=[(bm, bk), (bk, bn), (bk, bn)],
        out_blocks=[(bm, bn)],
        in_shapes=[(m, d), (d, f), (d, f)],
        out_shapes=[(m, f)],
        in_dtypes=[dtype] * 3,
        out_dtypes=[dtype],
        flops_per_step=4.0 * bm * bn * bk,         # two dots per step
        vpu_per_step=4.0 * bm * bn,                # act + gate multiply
        trans_per_step=1.0 * bm * bn,              # exp inside silu/gelu
        grid_steps=steps,
        scratch_bytes=2 * bm * bn * 4,             # gate + up f32 tiles
    )


@functools.partial(jax.jit,
                   static_argnames=("act", "bm", "bn", "bk", "interpret"))
def mlp_matmul_fused_pallas(x: jax.Array, w_gate: jax.Array,
                            w_up: jax.Array, act: str = "silu", *,
                            bm: int = 256, bn: int = 256, bk: int = 256,
                            interpret: bool | None = None) -> jax.Array:
    """x: (M, D); w_gate, w_up: (D, F) -> act(x@w_gate) * (x@w_up)."""
    if interpret is None:
        interpret = default_interpret()
    m, d = x.shape
    f = w_gate.shape[1]
    require_shape("mlp_matmul_fused_pallas", "w_gate", w_gate.shape, (d, f))
    require_shape("mlp_matmul_fused_pallas", "w_up", w_up.shape, (d, f))
    bm, bn, bk = min(bm, m), min(bn, f), min(bk, d)
    require_tiling("mlp_matmul_fused_pallas", {"m": m, "f": f, "d": d},
                   {"bm": bm, "bn": bn, "bk": bk})
    kern = functools.partial(_fused_kernel, act=act)
    return pl.pallas_call(
        kern,
        grid=(m // bm, f // bn, d // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_gate, w_up)


# ---------------------------------------------------------------------------
# "stream" variant: whole-D panels, no contraction tiling, no scratch
# ---------------------------------------------------------------------------


def _stream_kernel(x_ref, g_ref, u_ref, o_ref, *, act):
    xb = x_ref[...]
    gate = jnp.dot(xb, g_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(xb, u_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = (_MLP_ACTS[act](gate) * up).astype(o_ref.dtype)


def _stream_analysis(p, *, m: int, d: int, f: int, act: str = "silu",
                     dtype: str = "float32"):
    """Whole-D panels: one grid step per output tile, single output
    flush, no scratch buffer — per-step footprint scales with D.  The
    body's float32 tiles (both dot results, the activation and the gated
    product) live in VMEM beside the panels, so they count against it."""
    bm = np.minimum(np.asarray(p["bm"], dtype=np.int64), m)
    bn = np.minimum(np.asarray(p["bn"], dtype=np.int64), f)
    return dict(
        in_blocks=[(bm, d), (d, bn), (d, bn)],
        out_blocks=[(bm, bn)],
        in_shapes=[(m, d), (d, f), (d, f)],
        out_shapes=[(m, f)],
        in_dtypes=[dtype] * 3,
        out_dtypes=[dtype],
        flops_per_step=4.0 * bm * bn * d,
        vpu_per_step=4.0 * bm * bn,
        trans_per_step=1.0 * bm * bn,
        grid_steps=cdiv(m, bm) * cdiv(f, bn),
        scratch_bytes=4 * bm * bn * 4,
    )


@functools.partial(jax.jit, static_argnames=("act", "bm", "bn", "interpret"))
def mlp_matmul_stream_pallas(x: jax.Array, w_gate: jax.Array,
                             w_up: jax.Array, act: str = "silu", *,
                             bm: int = 256, bn: int = 256,
                             interpret: bool | None = None) -> jax.Array:
    """Stream schedule: grid (M/bm, F/bn), full-D operand panels per
    step, gated tile emitted in one shot (no accumulator carry)."""
    if interpret is None:
        interpret = default_interpret()
    m, d = x.shape
    f = w_gate.shape[1]
    require_shape("mlp_matmul_stream_pallas", "w_gate", w_gate.shape, (d, f))
    require_shape("mlp_matmul_stream_pallas", "w_up", w_up.shape, (d, f))
    bm, bn = min(bm, m), min(bn, f)
    require_tiling("mlp_matmul_stream_pallas", {"m": m, "f": f},
                   {"bm": bm, "bn": bn})
    kern = functools.partial(_stream_kernel, act=act)
    return pl.pallas_call(
        kern,
        grid=(m // bm, f // bn),
        in_specs=[pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((d, bn), lambda i, j: (0, j)),
                  pl.BlockSpec((d, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, w_gate, w_up)


# ---------------------------------------------------------------------------
# "split" variant: two single-accumulator passes + elementwise combine
# ---------------------------------------------------------------------------


def _split_mm_kernel(a_ref, b_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _split_analysis(p, *, m: int, d: int, f: int, act: str = "silu",
                    dtype: str = "float32"):
    """Two matmul passes (x read twice, one f32 accumulator each) plus
    an output-sized elementwise combine, folded into per-step averages
    over the doubled step count."""
    bm = np.minimum(np.asarray(p["bm"], dtype=np.int64), m)
    bn = np.minimum(np.asarray(p["bn"], dtype=np.int64), f)
    bk = np.minimum(np.asarray(p["bk"], dtype=np.int64), d)
    steps = 2 * cdiv(m, bm) * cdiv(f, bn) * cdiv(d, bk)
    return dict(
        in_blocks=[(bm, bk), (bk, bn)],
        out_blocks=[(bm, bn), (bm, bn)],     # f32 pass output + combine
        in_shapes=[(m, d), (d, f)],
        out_shapes=[(m, f), (m, f)],
        in_dtypes=[dtype, dtype],
        out_dtypes=["float32", dtype],
        flops_per_step=2.0 * bm * bn * bk,
        vpu_per_step=3.0 * bm * bn,          # act + multiply + cast, avg
        trans_per_step=0.5 * bm * bn,        # exp, one pass of the two
        grid_steps=steps,
        scratch_bytes=bm * bn * 4,           # single accumulator tile
    )


@functools.partial(jax.jit,
                   static_argnames=("act", "bm", "bn", "bk", "interpret"))
def mlp_matmul_split_pallas(x: jax.Array, w_gate: jax.Array,
                            w_up: jax.Array, act: str = "silu", *,
                            bm: int = 256, bn: int = 256, bk: int = 256,
                            interpret: bool | None = None) -> jax.Array:
    """Split schedule: gate and up matmuls as separate Pallas passes
    (f32 outputs), combined elementwise."""
    if interpret is None:
        interpret = default_interpret()
    m, d = x.shape
    f = w_gate.shape[1]
    require_shape("mlp_matmul_split_pallas", "w_gate", w_gate.shape, (d, f))
    require_shape("mlp_matmul_split_pallas", "w_up", w_up.shape, (d, f))
    bm, bn, bk = min(bm, m), min(bn, f), min(bk, d)
    require_tiling("mlp_matmul_split_pallas", {"m": m, "f": f, "d": d},
                   {"bm": bm, "bn": bn, "bk": bk})

    def one_pass(w):
        return pl.pallas_call(
            _split_mm_kernel,
            grid=(m // bm, f // bn, d // bk),
            in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                      pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, f), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(x, w)

    gate = one_pass(w_gate)
    up = one_pass(w_up)
    return (_MLP_ACTS[act](gate) * up).astype(x.dtype)


@tuned_kernel(
    "mlp_matmul",
    space={"bm": divisors("m", _SIZES),
           "bn": divisors("f", _SIZES),
           "bk": divisors("d", _SIZES)},
    signature=lambda x, w_gate, w_up, act="silu", **_: dict(
        m=x.shape[0], d=x.shape[1], f=w_gate.shape[1], act=act,
        dtype=str(x.dtype)),
    static_info=_fused_analysis,
    make_inputs=lambda key, *, m, d, f, act="silu", dtype="float32": tuple(
        jax.random.normal(k, shp, np.dtype(dtype))
        for k, shp in zip(jax.random.split(key, 3),
                          ((m, d), (d, f), (d, f)))),
    reference=mlp_matmul_ref,
    pretune=tuple(dict(m=m, d=d, f=f, act=act, dtype=dt)
                  for (m, d, f) in [(256, 512, 1024), (1024, 1024, 4096),
                                    (2048, 2048, 8192), (4096, 4096, 16384)]
                  for act in ("silu", "gelu")
                  for dt in ("float32", "bfloat16")),
    variants=(
        KernelVariant(
            variant_id="stream",
            fn=mlp_matmul_stream_pallas,
            space={"bm": divisors("m", _SIZES),
                   "bn": divisors("f", _SIZES)},
            analysis=_stream_analysis),
        KernelVariant(
            variant_id="split",
            fn=mlp_matmul_split_pallas,
            space={"bm": divisors("m", _SIZES),
                   "bn": divisors("f", _SIZES),
                   "bk": divisors("d", _SIZES)},
            analysis=_split_analysis),
    ),
    primary_variant="fused",
)
def mlp_matmul(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               act: str = "silu", *, bm: int = 256, bn: int = 256,
               bk: int = 256, interpret: bool | None = None) -> jax.Array:
    """Primary ("fused") implementation — see `mlp_matmul_fused_pallas`."""
    return mlp_matmul_fused_pallas(x, w_gate, w_up, act,
                                   bm=bm, bn=bn, bk=bk, interpret=interpret)
