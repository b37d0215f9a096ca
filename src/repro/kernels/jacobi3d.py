"""ex14FJ analogue (paper Table IV): 7-point 3-D Jacobi sweep in Pallas.

The volume (Z, Y, X) is swept in z-plane blocks of height ``bz``; the
same input is bound three times with index maps (i-1, i, i+1) (clamped
at the edges) so each grid step holds the previous / current / next
plane blocks in VMEM — the TPU version of a halo exchange.  Y/X stay
unblocked (paper problem sizes ≤ 512³ keep a plane ≤ 1 MB).  Dirichlet
boundaries pass through.

Tunables: bz (planes per grid step).
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
from repro.kernels.ref import jacobi3d_ref

__all__ = ["jacobi3d_pallas", "jacobi3d_static_info",
           "make_tunable_jacobi3d"]

C0_DEFAULT = 0.5
C1_DEFAULT = 1.0 / 12.0


def _jacobi_kernel(prev_ref, cur_ref, next_ref, o_ref, *, bz, z, c0, c1):
    i = pl.program_id(0)
    cur = cur_ref[...].astype(jnp.float32)
    prev = prev_ref[...].astype(jnp.float32)
    nxt = next_ref[...].astype(jnp.float32)

    # z-neighbours across the block boundary.
    up = jnp.concatenate([prev[-1:], cur[:-1]], axis=0)
    down = jnp.concatenate([cur[1:], nxt[:1]], axis=0)
    # in-plane shifts (zero-padded; boundaries are masked below anyway).
    north = jnp.pad(cur[:, :-1, :], ((0, 0), (1, 0), (0, 0)))
    south = jnp.pad(cur[:, 1:, :], ((0, 0), (0, 1), (0, 0)))
    west = jnp.pad(cur[:, :, :-1], ((0, 0), (0, 0), (1, 0)))
    east = jnp.pad(cur[:, :, 1:], ((0, 0), (0, 0), (0, 1)))

    out = c0 * cur + c1 * (up + down + north + south + west + east)

    # Dirichlet boundary: pass through on faces of the global volume.
    _, y, x = cur.shape
    gz = (i * bz + jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0))
    gy = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
    gx = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 2)
    interior = ((gz > 0) & (gz < z - 1) & (gy > 0) & (gy < y - 1)
                & (gx > 0) & (gx < x - 1))
    o_ref[...] = jnp.where(interior, out, cur).astype(o_ref.dtype)


def _jacobi3d_analysis(p, *, z: int, y: int, x: int,
                       dtype: str = "float32"):
    """Static analysis of one config (scalars) or a lattice ((N,) cols).

    7-point stencil: ~8 vector FLOPs/output; 3 block reads + 1 write.
    """
    bz = np.minimum(np.asarray(p["bz"], dtype=np.int64), z)
    steps = cdiv(z, bz)
    plane = y * x
    return dict(
        in_blocks=[(bz, y, x)] * 3,
        out_blocks=[(bz, y, x)],
        in_shapes=[(z, y, x)] * 3,
        out_shapes=[(z, y, x)],
        in_dtypes=[dtype] * 3,
        out_dtypes=[dtype],
        flops_per_step=0.0,
        vpu_per_step=8.0 * bz * plane,
        grid_steps=steps,
    )


def _jacobi3d_inputs(key, *, z: int, y: int, x: int,
                     dtype: str = "float32"):
    return (jax.random.normal(key, (z, y, x), np.dtype(dtype)),)


@tuned_kernel(
    "jacobi3d",
    space={"bz": divisors("z", (1, 2, 4, 8, 16, 32, 64))},
    signature=lambda u, **_: dict(z=u.shape[0], y=u.shape[1], x=u.shape[2],
                                  dtype=str(u.dtype)),
    static_info=_jacobi3d_analysis,
    make_inputs=_jacobi3d_inputs,
    reference=jacobi3d_ref,
    pretune=tuple(dict(z=s, y=s, x=s, dtype="float32")
                  for s in (64, 128, 256)),
    # Paper Table VII row (ex14FJ, the finite-difference Jacobi
    # kernel): R^u per compute capability, no shared memory; 7-point
    # stencil = 8 flops/point, read + write per point.
    cuda=cuda_profile(
        regs={"Fermi": 30, "Kepler": 31, "Maxwell": 28},
        workload=lambda z, y, x, **_: dict(
            o_fl=8.0 * z * y * x, o_mem=2.0 * z * y * x,
            o_ctrl=1.0 * z, o_reg=8.0 * z * y * x)),
)
@functools.partial(jax.jit,
                   static_argnames=("bz", "c0", "c1", "interpret"))
def jacobi3d_pallas(u: jax.Array, *, bz: int = 8,
                    c0: float = C0_DEFAULT, c1: float = C1_DEFAULT,
                    interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    z, y, x = u.shape
    bz = min(bz, z)
    require_tiling("jacobi3d_pallas", {"z": z}, {"bz": bz})
    nb = z // bz
    kern = functools.partial(_jacobi_kernel, bz=bz, z=z, c0=c0, c1=c1)
    clamp = lambda v, hi: jnp.minimum(jnp.maximum(v, 0), hi)
    return pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bz, y, x), lambda i: (clamp(i - 1, nb - 1), 0, 0)),
            pl.BlockSpec((bz, y, x), lambda i: (i, 0, 0)),
            pl.BlockSpec((bz, y, x), lambda i: (clamp(i + 1, nb - 1), 0, 0)),
        ],
        out_specs=pl.BlockSpec((bz, y, x), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((z, y, x), u.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(u, u, u)


def jacobi3d_static_info(z: int, y: int, x: int, dtype,
                         params: Dict) -> KernelStaticInfo:
    """Scalar static info for one configuration (wrapper over the
    declared analysis; kept as a stable public helper)."""
    return block_info(**_jacobi3d_analysis(params, z=z, y=y, x=x,
                                           dtype=dtype))


def make_tunable_jacobi3d(z: int = 128, y: int = 128, x: int = 128,
                          dtype=jnp.float32, seed: int = 0) -> TunableKernel:
    space = SearchSpace({
        "bz": pick_divisor_candidates(z, (1, 2, 4, 8, 16, 32, 64)),
    })
    return get_spec("jacobi3d").tunable(
        z=z, y=y, x=x, dtype=np.dtype(dtype).name, seed=seed,
        space=space, name=f"jacobi3d_{z}x{y}x{x}")
