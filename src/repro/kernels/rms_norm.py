"""RMSNorm Pallas kernel (row-blocked, VPU-bound).

The third op on the LM serving path (`repro.models.layers.rms_norm`
routes here when tuned layers are enabled).  Grid (M/bm,) over the
flattened token axis; each step normalizes a (bm, D) row block in f32
with `jax.lax.rsqrt` — the exact float discipline of the jnp reference
path, so the tuned route is numerically indistinguishable from the
fallback.

Tunable: bm (row-block size).  Single implementation — variant
dispatch is for ops where schedules genuinely compete.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.api import divisors, tuned_kernel
from repro.kernels.common import (cdiv, default_interpret, require_shape,
                                  require_tiling)
from repro.kernels.ref import rms_norm_ref

__all__ = ["rms_norm_pallas"]


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    xf = x_ref[...].astype(jnp.float32)            # (bm, d)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    w = w_ref[...].astype(jnp.float32)             # (1, d)
    o_ref[...] = (xf * jax.lax.rsqrt(var + eps) * w).astype(o_ref.dtype)


def _rms_analysis(p, *, m: int, d: int, dtype: str = "float32"):
    """Static analysis of one config (scalars) or a lattice ((N,) cols).
    Pure VPU workload: square, mean, rsqrt-scale, weight multiply."""
    bm = np.minimum(np.asarray(p["bm"], dtype=np.int64), m)
    return dict(
        in_blocks=[(bm, d), (1, d)],
        out_blocks=[(bm, d)],
        in_shapes=[(m, d), (1, d)],
        out_shapes=[(m, d)],
        in_dtypes=[dtype, dtype],
        out_dtypes=[dtype],
        flops_per_step=0.0,
        vpu_per_step=6.0 * bm * d,        # sq, sum, scale, mul, casts
        trans_per_step=1.0 * bm,          # rsqrt per row
        grid_steps=cdiv(m, bm),
    )


@tuned_kernel(
    "rms_norm",
    space={"bm": divisors("m", (8, 16, 32, 64, 128, 256, 512, 1024))},
    signature=lambda x, w, **_: dict(m=x.shape[0], d=x.shape[1],
                                     dtype=str(x.dtype)),
    static_info=_rms_analysis,
    make_inputs=lambda key, *, m, d, dtype="float32": tuple(
        jax.random.normal(k, shp, np.dtype(dtype))
        for k, shp in zip(jax.random.split(key), ((m, d), (d,)))),
    reference=rms_norm_ref,
    pretune=tuple(dict(m=m, d=d, dtype=dt)
                  for (m, d) in [(1024, 1024), (4096, 4096), (8192, 2048)]
                  for dt in ("float32", "bfloat16")),
)
@functools.partial(jax.jit, static_argnames=("eps", "bm", "interpret"))
def rms_norm_pallas(x: jax.Array, w: jax.Array, eps: float = 1e-6, *,
                    bm: int = 256,
                    interpret: bool | None = None) -> jax.Array:
    """x: (M, D), w: (D,) -> (M, D) RMS-normalized rows."""
    if interpret is None:
        interpret = default_interpret()
    m, d = x.shape
    require_shape("rms_norm_pallas", "w", w.shape, (d,))
    bm = min(bm, m)
    require_tiling("rms_norm_pallas", {"m": m}, {"bm": bm})
    kern = functools.partial(_rms_kernel, eps=eps)
    return pl.pallas_call(
        kern,
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w.reshape(1, d))
