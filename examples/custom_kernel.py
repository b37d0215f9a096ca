"""Bring your own kernel: one `@tuned_kernel` declaration makes any
Pallas kernel a first-class tuning citizen.

    PYTHONPATH=src python examples/custom_kernel.py [--smoke]

This file is the whole integration: no edits to ops.py, registry.py,
or the CLI.  The declaration below derives

* trace-time dispatch (cold full-space rank, then warm memoized hits),
* the dispatch-registry problem (`tuning_cache.get_problem` /
  `lookup_or_tune`, CLI `tune --kernel saxpy2d ...`),
* `KernelTuner` packaging (static / hybrid / empirical modes),
* largest-divisor fallback params if the database is unavailable.
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import tuning_cache
from repro.core import KernelTuner
from repro.kernels.api import divisors, get_spec, tuned_kernel
from repro.kernels.common import cdiv, default_interpret, require_tiling


# -- 1. the kernel body: a row-blocked fused scale-add ----------------------

def _saxpy_kernel(a_ref, b_ref, o_ref, *, alpha):
    o_ref[...] = alpha * a_ref[...] + b_ref[...]


# -- 2. the static analyzer: one array-agnostic function ---------------------
# `p["bm"]` is a scalar when dispatch probes one config and an (N,)
# column when the cold rank scores the whole lattice — same code.

def _saxpy_analysis(p, *, m: int, n: int, dtype: str = "float32"):
    bm = np.minimum(np.asarray(p["bm"], dtype=np.int64), m)
    return dict(
        in_blocks=[(bm, n), (bm, n)],
        out_blocks=[(bm, n)],
        in_dtypes=[dtype, dtype],
        out_dtypes=[dtype],
        flops_per_step=0.0,
        vpu_per_step=2.0 * bm * n,        # one mul + one add per element
        grid_steps=cdiv(m, bm),
    )


def _saxpy_inputs(key, *, m: int, n: int, dtype: str = "float32"):
    ka, kb = jax.random.split(key)
    dt = np.dtype(dtype)
    return (jax.random.normal(ka, (m, n), dt),
            jax.random.normal(kb, (m, n), dt))


# -- 3. the declaration: everything else is derived --------------------------

@tuned_kernel(
    "saxpy2d",
    space={"bm": divisors("m", (8, 16, 32, 64, 128, 256, 512))},
    signature=lambda a, b, **_: dict(m=a.shape[0], n=a.shape[1],
                                     dtype=str(a.dtype)),
    static_info=_saxpy_analysis,
    make_inputs=_saxpy_inputs,
    reference=lambda a, b: 2.0 * a + b,
)
@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def saxpy2d_pallas(a: jax.Array, b: jax.Array, *, bm: int = 128,
                   interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    m, n = a.shape
    bm = min(bm, m)
    require_tiling("saxpy2d_pallas", {"m": m}, {"bm": bm})
    return pl.pallas_call(
        functools.partial(_saxpy_kernel, alpha=2.0),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0)),
                  pl.BlockSpec((bm, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(a, b)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / skip the empirical sweep (CI)")
    args = ap.parse_args(argv)
    m, n = (256, 256) if args.smoke else (2048, 1024)

    spec = get_spec("saxpy2d")
    a = jnp.ones((m, n), jnp.float32)
    b = jnp.ones((m, n), jnp.float32)

    print("== trace-time dispatch: cold rank, then warm memo hits ==")
    out = spec.op(a, b)                     # first call tunes
    np.testing.assert_allclose(out, 2.0 * a + b)
    for _ in range(3):
        spec.op(a, b)                       # pure cache/memo hits
    db = tuning_cache.get_default_db()
    params = tuning_cache.lookup_or_tune("saxpy2d", m=m, n=n,
                                         dtype="float32")
    print(f"   resolved params: {params}  db stats: "
          f"{db.stats.as_dict()}")
    assert db.stats.tunes <= 1, "warm dispatch must not re-tune"

    print("\n== the same declaration drives the full KernelTuner ==")
    tk = spec.tunable(m=m, n=n, dtype="float32")
    rep = KernelTuner(tk, repeats=1).tune(mode="static")
    print("   " + rep.summary())
    assert rep.empirical_evals == 0

    if not args.smoke:
        rep_h = KernelTuner(tk, repeats=2).tune(mode="hybrid",
                                                empirical_budget=2)
        print("   " + rep_h.summary())

    print("\n== fallback params (database unavailable) ==")
    print(f"   {spec.fallback_params(m=m, n=n)}")
    print("\nOK: one decorated module, zero edits elsewhere.")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
