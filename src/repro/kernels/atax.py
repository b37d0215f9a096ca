"""atax (paper Table IV): y = Aᵀ(A x), single-pass fused Pallas kernel.

Key identity: y = Aᵀ(Ax) = Σ_i A_iᵀ (A_i x) over row blocks A_i, so one
sequential sweep over row blocks computes the fused result with A read
exactly **once** — twice the arithmetic intensity of the two-matmul
formulation.  x and the y accumulator live in VMEM for the whole sweep.

Tunables: bm (row-block height).
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
                                  pick_divisor_candidates, require_shape,
                                  require_tiling)
from repro.kernels.ref import atax_ref

__all__ = ["atax_pallas", "atax_static_info", "make_tunable_atax"]


def _atax_kernel_rowsweep(a_ref, x_ref, y_ref, acc_ref):
    """Row-block sweep with full-width rows: per step,
    t = A_blk @ x; y_acc += A_blkᵀ t.  A is read once."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_blk = a_ref[...]
    t = jnp.dot(a_blk, x_ref[...], preferred_element_type=jnp.float32)
    acc_ref[...] += jnp.dot(a_blk.T, t.astype(a_blk.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _atax_analysis(p, *, m: int, n: int, dtype: str = "float32"):
    """Static analysis of one config (scalars) or a lattice ((N,) cols)."""
    bm = np.minimum(np.asarray(p["bm"], dtype=np.int64), m)
    steps = cdiv(m, bm)
    return dict(
        in_blocks=[(bm, n), (n, 1)],
        out_blocks=[(n, 1)],
        in_shapes=[(m, n), (n, 1)],
        out_shapes=[(n, 1)],
        in_dtypes=[dtype, dtype],
        out_dtypes=[dtype],
        flops_per_step=2.0 * bm * n + 2.0 * n * bm,   # A@x then Aᵀ@t
        grid_steps=steps,
        scratch_bytes=n * 4,
    )


def _atax_inputs(key, *, m: int, n: int, dtype: str = "float32"):
    ka, kx = jax.random.split(key)
    dt = np.dtype(dtype)
    return (jax.random.normal(ka, (m, n), dt) / (n ** 0.5),
            jax.random.normal(kx, (n, 1), dt))


@tuned_kernel(
    "atax",
    space={"bm": divisors("m", (16, 32, 64, 128, 256, 512, 1024))},
    signature=lambda a, x, **_: dict(m=a.shape[0], n=a.shape[1],
                                     dtype=str(a.dtype)),
    static_info=_atax_analysis,
    make_inputs=_atax_inputs,
    reference=atax_ref,
    pretune=tuple(dict(m=s, n=s, dtype=dt)
                  for s in (512, 1024, 2048, 4096)
                  for dt in ("float32", "bfloat16"))
    + (dict(m=1024, n=512, dtype="float32"),),
    # Paper Table VII row: R^u per compiled compute capability; no
    # shared memory.  Whole-kernel Eq. 6 counts: A read once, fused
    # A@x then A^T@t (4 flops/element), y accumulated in registers.
    cuda=cuda_profile(
        regs={"Fermi": 21, "Kepler": 27, "Maxwell": 30},
        workload=lambda m, n, **_: dict(
            o_fl=4.0 * m * n, o_mem=1.0 * m * n + m + 2.0 * n,
            o_ctrl=1.0 * m, o_reg=4.0 * m * n)),
)
@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def atax_pallas(a: jax.Array, x: jax.Array, *, bm: int = 256,
                interpret: bool | None = None) -> jax.Array:
    """y = Aᵀ(Ax).  a: (M, N), x: (N, 1) -> y: (N, 1).

    Row stripes are full-width (the paper's kernels are skinny:
    N ≤ 4096 keeps the stripe + x + y-accumulator well inside VMEM).
    """
    if interpret is None:
        interpret = default_interpret()
    m, n = a.shape
    require_shape("atax_pallas", "x", x.shape, (n, 1))
    bm = min(bm, m)
    require_tiling("atax_pallas", {"m": m}, {"bm": bm})
    grid = (m // bm,)
    return pl.pallas_call(
        _atax_kernel_rowsweep,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0)),
                  pl.BlockSpec((n, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((n, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), a.dtype),
        scratch_shapes=[pltpu.VMEM((n, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(a, x)


def atax_static_info(m: int, n: int, dtype, params: Dict
                     ) -> KernelStaticInfo:
    """Scalar static info for one configuration (wrapper over the
    declared analysis; kept as a stable public helper)."""
    return block_info(**_atax_analysis(params, m=m, n=n, dtype=dtype))


def make_tunable_atax(m: int = 2048, n: int = 2048,
                      dtype=jnp.float32, seed: int = 0) -> TunableKernel:
    space = SearchSpace({
        "bm": pick_divisor_candidates(m, (32, 64, 128, 256, 512, 1024)),
    })
    return get_spec("atax").tunable(
        m=m, n=n, dtype=np.dtype(dtype).name, seed=seed,
        space=space, name=f"atax_{m}x{n}")
