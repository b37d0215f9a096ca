"""Blocked matmul Pallas kernel (the MXU workhorse).

Grid (M/bm, N/bn, K/bk) with an f32 VMEM accumulator tile; the K axis
is the innermost, ``arbitrary`` (sequential) grid dimension so the
accumulator carries across K steps — the canonical TPU tiling.

Tunables (the Table III analogue): bm, bn, bk.  The whole tuning stack
(dispatch wrapper, registry problem, tunable-kernel packaging, fallback
params, pretune grid) derives from the single `@tuned_kernel`
declaration below.
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
from repro.kernels.api import cuda_profile, divisors, get_spec, tuned_kernel
from repro.kernels.common import (block_info, cdiv, default_interpret,
                                  pick_divisor_candidates, require_tiling)
from repro.kernels.ref import matmul_ref

__all__ = ["matmul_pallas", "matmul_static_info", "make_tunable_matmul"]


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _matmul_analysis(p, *, m: int, n: int, k: int, dtype: str = "float32"):
    """Static analysis of one config (scalars) or a lattice ((N,) cols)."""
    bm = np.minimum(np.asarray(p["bm"], dtype=np.int64), m)
    bn = np.minimum(np.asarray(p["bn"], dtype=np.int64), n)
    bk = np.minimum(np.asarray(p["bk"], dtype=np.int64), k)
    steps = cdiv(m, bm) * cdiv(n, bn) * cdiv(k, bk)
    return dict(
        in_blocks=[(bm, bk), (bk, bn)],
        out_blocks=[(bm, bn)],
        in_shapes=[(m, k), (k, n)],
        out_shapes=[(m, n)],
        in_dtypes=[dtype, dtype],
        out_dtypes=[dtype],
        flops_per_step=2.0 * bm * bn * bk,
        grid_steps=steps,
        scratch_bytes=bm * bn * 4,
    )


def _matmul_schedule(p, *, m: int, n: int, k: int, dtype: str = "float32"):
    """Per-K-step instruction stream for the pipeline tier (DESIGN.md
    §16): the kernel's actual phase order — stage both operand tiles,
    MXU-contract into the VMEM accumulator, amortized result flush —
    rather than the synthesized class-ordered stream.  Row format:
    ``(class, units[, dep])`` with ``dep`` an index into the stream."""
    bm = min(int(p["bm"]), m)
    bn = min(int(p["bn"]), n)
    bk = min(int(p["bk"]), k)
    eb = np.dtype(dtype).itemsize
    return [
        ("hbm", float((bm * bk + bk * bn) * eb)),          # 0: tile DMA in
        ("vmem", float((bm * bk + bk * bn + bm * bn) * eb), 0),  # 1: staging
        ("mxu", 2.0 * bm * bn * bk, 1),                    # 2: contraction
        # result tile leaves once per (i, j) cell, i.e. every K/bk steps
        ("hbm", float(bm * bn * eb) / max(cdiv(k, bk), 1)),
        ("ctrl", 1.0),                                     # grid bookkeeping
    ]


def _matmul_inputs(key, *, m: int, n: int, k: int, dtype: str = "float32"):
    ka, kb = jax.random.split(key)
    dt = np.dtype(dtype)
    return (jax.random.normal(ka, (m, k), dt),
            jax.random.normal(kb, (k, n), dt))


@tuned_kernel(
    "matmul",
    space={"bm": divisors("m", (8, 16, 32, 64, 128, 256, 512)),
           "bn": divisors("n", (8, 16, 32, 64, 128, 256, 512)),
           "bk": divisors("k", (8, 16, 32, 64, 128, 256, 512))},
    signature=lambda a, b, **_: dict(m=a.shape[0], n=b.shape[1],
                                     k=a.shape[1], dtype=str(a.dtype)),
    static_info=_matmul_analysis,
    schedule=_matmul_schedule,
    make_inputs=_matmul_inputs,
    reference=matmul_ref,
    pretune=tuple(dict(m=m, n=n, k=k, dtype=dt)
                  for (m, n, k) in [(256,) * 3, (512,) * 3, (1024,) * 3,
                                    (2048,) * 3, (1024, 1024, 4096),
                                    (4096, 1024, 1024)]
                  for dt in ("float32", "bfloat16")),
    # Not a paper kernel; classic shared-memory-tiled SGEMM numbers:
    # two 16x16 f32 operand tiles staged per block, moderate register
    # pressure (accumulator + tile indices).
    cuda=cuda_profile(
        regs=32, shmem_per_block=2 * 16 * 16 * 4,
        workload=lambda m, n, k, **_: dict(
            o_fl=2.0 * m * n * k, o_mem=1.0 * (m * k + k * n + m * n),
            o_ctrl=1.0 * m * n, o_reg=2.0 * m * n * k)),
)
@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_pallas(a: jax.Array, b: jax.Array, *,
                  bm: int = 256, bn: int = 256, bk: int = 256,
                  interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    m, k = a.shape
    k2, n = b.shape
    if k2 != k:
        raise ValueError(f"matmul_pallas: inner dimensions disagree: "
                         f"a.shape={tuple(a.shape)}, b.shape={tuple(b.shape)}")
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    require_tiling("matmul_pallas", {"m": m, "n": n, "k": k},
                   {"bm": bm, "bn": bn, "bk": bk})
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _mm_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)


def matmul_static_info(m: int, n: int, k: int, dtype,
                       params: Dict) -> KernelStaticInfo:
    """Scalar static info for one configuration (wrapper over the
    declared analysis; kept as a stable public helper)."""
    return block_info(**_matmul_analysis(params, m=m, n=n, k=k, dtype=dtype))


def make_tunable_matmul(m: int = 1024, n: int = 1024, k: int = 1024,
                        dtype=jnp.float32, seed: int = 0) -> TunableKernel:
    sizes = (128, 256, 512)
    space = SearchSpace({
        "bm": pick_divisor_candidates(m, sizes),
        "bn": pick_divisor_candidates(n, sizes),
        "bk": pick_divisor_candidates(k, sizes),
    })
    return get_spec("matmul").tunable(
        m=m, n=n, k=k, dtype=np.dtype(dtype).name, seed=seed,
        space=space, name=f"matmul_{m}x{n}x{k}")
