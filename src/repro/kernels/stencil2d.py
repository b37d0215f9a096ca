"""stencil2d: 5-point 2-D Jacobi sweep — the openness proof for
`@tuned_kernel`.

The Jacobi-family analogue from the paper's benchmark suite, added as a
*new* workload after the API redesign: this module is the **only** file
that knows stencil2d exists, yet the kernel gets cold full-space
ranking, per-target pretuned records, warm memoized dispatch
(``repro.kernels.ops.stencil2d``), and `KernelTuner` packaging — all
derived from the single declaration below.  Nothing in ``ops.py``,
``registry.py``, or the CLI names it.

The grid (Y, X) is swept in row blocks of height ``by``; the input is
bound three times with clamped index maps (i-1, i, i+1) so each grid
step holds the previous / current / next row blocks in VMEM (the same
halo-exchange idiom as jacobi3d, one dimension down).  Dirichlet
boundaries pass through.  The oracle lives here too, keeping the
zero-edits-elsewhere claim literal.

Tunables: by (rows per grid step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.api import cuda_profile, divisors, get_spec, tuned_kernel
from repro.kernels.common import cdiv, default_interpret, require_tiling

__all__ = ["stencil2d_pallas", "stencil2d_ref", "make_tunable_stencil2d"]

C0_DEFAULT = 0.5
C1_DEFAULT = 0.125


def stencil2d_ref(u: jax.Array, c0: float = C0_DEFAULT,
                  c1: float = C1_DEFAULT) -> jax.Array:
    """Pure-jnp oracle: out = c0*u + c1*(4 edge neighbours) on the
    interior; boundary cells pass through unchanged."""
    f = u.astype(jnp.float32)
    interior = (c0 * f[1:-1, 1:-1]
                + c1 * (f[:-2, 1:-1] + f[2:, 1:-1]
                        + f[1:-1, :-2] + f[1:-1, 2:]))
    return f.at[1:-1, 1:-1].set(interior).astype(u.dtype)


def _stencil_kernel(prev_ref, cur_ref, next_ref, o_ref, *, by, y, c0, c1):
    i = pl.program_id(0)
    cur = cur_ref[...].astype(jnp.float32)          # (by, x)
    prev = prev_ref[...].astype(jnp.float32)
    nxt = next_ref[...].astype(jnp.float32)

    # row neighbours across the block boundary.
    up = jnp.concatenate([prev[-1:], cur[:-1]], axis=0)
    down = jnp.concatenate([cur[1:], nxt[:1]], axis=0)
    # in-row shifts (zero-padded; boundaries are masked below anyway).
    west = jnp.pad(cur[:, :-1], ((0, 0), (1, 0)))
    east = jnp.pad(cur[:, 1:], ((0, 0), (0, 1)))

    out = c0 * cur + c1 * (up + down + west + east)

    # Dirichlet boundary: pass through on the edges of the global grid.
    _, x = cur.shape
    gy = i * by + jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    gx = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
    interior = (gy > 0) & (gy < y - 1) & (gx > 0) & (gx < x - 1)
    o_ref[...] = jnp.where(interior, out, cur).astype(o_ref.dtype)


def _stencil2d_analysis(p, *, y: int, x: int, dtype: str = "float32"):
    """Static analysis of one config (scalars) or a lattice ((N,) cols).

    5-point stencil: ~6 vector FLOPs/output; 3 block reads + 1 write.
    """
    by = np.minimum(np.asarray(p["by"], dtype=np.int64), y)
    steps = cdiv(y, by)
    return dict(
        in_blocks=[(by, x)] * 3,
        out_blocks=[(by, x)],
        in_shapes=[(y, x)] * 3,
        out_shapes=[(y, x)],
        in_dtypes=[dtype] * 3,
        out_dtypes=[dtype],
        flops_per_step=0.0,
        vpu_per_step=6.0 * by * x,
        grid_steps=steps,
    )


def _stencil2d_inputs(key, *, y: int, x: int, dtype: str = "float32"):
    return (jax.random.normal(key, (y, x), np.dtype(dtype)),)


@tuned_kernel(
    "stencil2d",
    space={"by": divisors("y", (8, 16, 32, 64, 128, 256))},
    signature=lambda u, **_: dict(y=u.shape[0], x=u.shape[1],
                                  dtype=str(u.dtype)),
    static_info=_stencil2d_analysis,
    make_inputs=_stencil2d_inputs,
    reference=stencil2d_ref,
    pretune=(dict(y=512, x=512, dtype="float32"),
             dict(y=1024, x=1024, dtype="float32"),
             dict(y=2048, x=2048, dtype="float32"),
             dict(y=1024, x=1024, dtype="bfloat16")),
    # 5-point Jacobi: 6 flops/point, read + write per point, light
    # register pressure (no staging).
    cuda=cuda_profile(
        regs=24,
        workload=lambda y, x, **_: dict(
            o_fl=6.0 * y * x, o_mem=2.0 * y * x,
            o_ctrl=1.0 * y, o_reg=6.0 * y * x)),
)
@functools.partial(jax.jit,
                   static_argnames=("by", "c0", "c1", "interpret"))
def stencil2d_pallas(u: jax.Array, *, by: int = 32,
                     c0: float = C0_DEFAULT, c1: float = C1_DEFAULT,
                     interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    y, x = u.shape
    by = min(by, y)
    require_tiling("stencil2d_pallas", {"y": y}, {"by": by})
    nb = y // by
    kern = functools.partial(_stencil_kernel, by=by, y=y, c0=c0, c1=c1)
    clamp = lambda v, hi: jnp.minimum(jnp.maximum(v, 0), hi)
    return pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((by, x), lambda i: (clamp(i - 1, nb - 1), 0)),
            pl.BlockSpec((by, x), lambda i: (i, 0)),
            pl.BlockSpec((by, x), lambda i: (clamp(i + 1, nb - 1), 0)),
        ],
        out_specs=pl.BlockSpec((by, x), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((y, x), u.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(u, u, u)


def make_tunable_stencil2d(y: int = 512, x: int = 512, dtype=jnp.float32,
                           seed: int = 0):
    """Tunable-kernel packaging over the *full* dispatch space — the
    decorated path needs no hand-picked narrower grid."""
    return get_spec("stencil2d").tunable(
        y=y, x=x, dtype=np.dtype(dtype).name, seed=seed)
