"""MatVec2D (paper Table IV): y = A x as a Pallas kernel.

Grid (M/bm, N/bk): row blocks parallel, column blocks sequential with an
f32 accumulator column.  The vector is carried as (N, 1); the static
analyzer's MXU-alignment model shows the n=1 lane-padding waste that
makes mat-vec memory-bound — the paper's "matVec2D prefers higher thread
settings" observation maps to wider row blocks here.
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
from repro.kernels.ref import matvec_ref

__all__ = ["matvec_pallas", "matvec_static_info", "make_tunable_matvec"]


def _mv_kernel(a_ref, x_ref, y_ref, acc_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], x_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _matvec_analysis(p, *, m: int, n: int, dtype: str = "float32"):
    """Static analysis of one config (scalars) or a lattice ((N,) cols)."""
    bm = np.minimum(np.asarray(p["bm"], dtype=np.int64), m)
    bk = np.minimum(np.asarray(p["bk"], dtype=np.int64), n)
    steps = cdiv(m, bm) * cdiv(n, bk)
    return dict(
        in_blocks=[(bm, bk), (bk, 1)],
        out_blocks=[(bm, 1)],
        in_shapes=[(m, n), (n, 1)],
        out_shapes=[(m, 1)],
        in_dtypes=[dtype, dtype],
        out_dtypes=[dtype],
        flops_per_step=2.0 * bm * bk,
        grid_steps=steps,
        scratch_bytes=bm * 4,
    )


def _matvec_inputs(key, *, m: int, n: int, dtype: str = "float32"):
    ka, kx = jax.random.split(key)
    dt = np.dtype(dtype)
    return (jax.random.normal(ka, (m, n), dt),
            jax.random.normal(kx, (n, 1), dt))


@tuned_kernel(
    "matvec",
    space={"bm": divisors("m", (32, 64, 128, 256, 512, 1024)),
           "bk": divisors("n", (32, 64, 128, 256, 512, 1024))},
    signature=lambda a, x, **_: dict(m=a.shape[0], n=a.shape[1],
                                     dtype=str(a.dtype)),
    static_info=_matvec_analysis,
    make_inputs=_matvec_inputs,
    reference=matvec_ref,
    pretune=tuple(dict(m=s, n=s, dtype=dt)
                  for s in (512, 1024, 2048, 4096)
                  for dt in ("float32", "bfloat16")),
    # Paper Table VII row (matVec2D): R^u per compute capability, no
    # shared memory; one multiply-add per matrix element.
    cuda=cuda_profile(
        regs={"Fermi": 20, "Kepler": 20, "Maxwell": 13},
        workload=lambda m, n, **_: dict(
            o_fl=2.0 * m * n, o_mem=1.0 * m * n + m + n,
            o_ctrl=1.0 * m, o_reg=2.0 * m * n)),
)
@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def matvec_pallas(a: jax.Array, x: jax.Array, *,
                  bm: int = 256, bk: int = 512,
                  interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    m, n = a.shape
    require_shape("matvec_pallas", "x", x.shape, (n, 1))
    bm, bk = min(bm, m), min(bk, n)
    require_tiling("matvec_pallas", {"m": m, "n": n}, {"bm": bm, "bk": bk})
    grid = (m // bm, n // bk)
    return pl.pallas_call(
        _mv_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
                  pl.BlockSpec((bk, 1), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, x)


def matvec_static_info(m: int, n: int, dtype, params: Dict
                       ) -> KernelStaticInfo:
    """Scalar static info for one configuration (wrapper over the
    declared analysis; kept as a stable public helper)."""
    return block_info(**_matvec_analysis(params, m=m, n=n, dtype=dtype))


def make_tunable_matvec(m: int = 2048, n: int = 2048,
                        dtype=jnp.float32, seed: int = 0) -> TunableKernel:
    space = SearchSpace({
        "bm": pick_divisor_candidates(m, (64, 128, 256, 512, 1024)),
        "bk": pick_divisor_candidates(n, (128, 256, 512, 1024)),
    })
    return get_spec("matvec").tunable(
        m=m, n=n, dtype=np.dtype(dtype).name, seed=seed,
        space=space, name=f"matvec_{m}x{n}")
