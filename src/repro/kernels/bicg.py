"""BiCG subkernel (paper Table IV): q = A p, s = Aᵀ r — fused.

One sequential sweep over row blocks: each step emits the q block for
those rows and accumulates the sᵀ partial, reading A once (vs twice for
separate matvecs).  Same fusion argument as atax.
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
from repro.kernels.ref import bicg_ref

__all__ = ["bicg_pallas", "bicg_static_info", "make_tunable_bicg"]


def _bicg_kernel(a_ref, p_ref, r_ref, q_ref, s_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_blk = a_ref[...]
    q_ref[...] = jnp.dot(a_blk, p_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(q_ref.dtype)
    acc_ref[...] += jnp.dot(a_blk.T, r_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        s_ref[...] = acc_ref[...].astype(s_ref.dtype)


def _bicg_analysis(p, *, m: int, n: int, dtype: str = "float32"):
    """Static analysis of one config (scalars) or a lattice ((N,) cols)."""
    bm = np.minimum(np.asarray(p["bm"], dtype=np.int64), m)
    steps = cdiv(m, bm)
    return dict(
        in_blocks=[(bm, n), (n, 1), (bm, 1)],
        out_blocks=[(bm, 1), (n, 1)],
        in_shapes=[(m, n), (n, 1), (m, 1)],
        out_shapes=[(m, 1), (n, 1)],
        in_dtypes=[dtype] * 3,
        out_dtypes=[dtype] * 2,
        flops_per_step=4.0 * bm * n,     # two mat-vec MACs over the block
        grid_steps=steps,
        scratch_bytes=n * 4,
    )


def _bicg_inputs(key, *, m: int, n: int, dtype: str = "float32"):
    ka, kp, kr = jax.random.split(key, 3)
    dt = np.dtype(dtype)
    return (jax.random.normal(ka, (m, n), dt) / (n ** 0.5),
            jax.random.normal(kp, (n, 1), dt),
            jax.random.normal(kr, (m, 1), dt))


@tuned_kernel(
    "bicg",
    space={"bm": divisors("m", (16, 32, 64, 128, 256, 512, 1024))},
    signature=lambda a, p, r, **_: dict(m=a.shape[0], n=a.shape[1],
                                        dtype=str(a.dtype)),
    static_info=_bicg_analysis,
    make_inputs=_bicg_inputs,
    reference=bicg_ref,
    pretune=tuple(dict(m=s, n=s, dtype=dt)
                  for s in (512, 1024, 2048, 4096)
                  for dt in ("float32", "bfloat16")),
    # Paper Table VII row (BiCG kernel of the sub-solver): R^u per
    # compute capability, no shared memory; A read once for both
    # products (4 flops/element), two vector reads + two writes.
    cuda=cuda_profile(
        regs={"Fermi": 27, "Kepler": 28, "Maxwell": 32},
        workload=lambda m, n, **_: dict(
            o_fl=4.0 * m * n, o_mem=1.0 * m * n + 2.0 * (m + n),
            o_ctrl=1.0 * m, o_reg=4.0 * m * n)),
)
@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def bicg_pallas(a: jax.Array, p: jax.Array, r: jax.Array, *,
                bm: int = 256, interpret: bool | None = None):
    """a: (M, N), p: (N, 1), r: (M, 1) -> (q: (M, 1), s: (N, 1))."""
    if interpret is None:
        interpret = default_interpret()
    m, n = a.shape
    require_shape("bicg_pallas", "p", p.shape, (n, 1))
    require_shape("bicg_pallas", "r", r.shape, (m, 1))
    bm = min(bm, m)
    require_tiling("bicg_pallas", {"m": m}, {"bm": bm})
    grid = (m // bm,)
    return pl.pallas_call(
        _bicg_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0)),
                  pl.BlockSpec((n, 1), lambda i: (0, 0)),
                  pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                   pl.BlockSpec((n, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((m, 1), a.dtype),
                   jax.ShapeDtypeStruct((n, 1), a.dtype)],
        scratch_shapes=[pltpu.VMEM((n, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(a, p, r)


def bicg_static_info(m: int, n: int, dtype, params: Dict
                     ) -> KernelStaticInfo:
    """Scalar static info for one configuration (wrapper over the
    declared analysis; kept as a stable public helper)."""
    return block_info(**_bicg_analysis(params, m=m, n=n, dtype=dtype))


def make_tunable_bicg(m: int = 2048, n: int = 2048,
                      dtype=jnp.float32, seed: int = 0) -> TunableKernel:
    space = SearchSpace({
        "bm": pick_divisor_candidates(m, (32, 64, 128, 256, 512, 1024)),
    })
    return get_spec("bicg").tunable(
        m=m, n=n, dtype=np.dtype(dtype).name, seed=seed,
        space=space, name=f"bicg_{m}x{n}")
