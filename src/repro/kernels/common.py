"""Shared helpers for the Pallas kernel layer.

Every kernel in this package follows the same contract:

* ``<name>_pallas(...)`` — the ``pl.pallas_call`` with explicit
  BlockSpec VMEM tiling, TPU as the lowering target; ``interpret=True``
  executes the same kernel body on CPU for validation.
* an analytic ``static_info`` builder that derives the instruction mix
  and TPU occupancy of a given launch configuration **without running
  or compiling anything** — the static-analyzer input for the tuner.
* ``make_tunable(...)`` — packages the kernel as a
  :class:`repro.core.autotuner.TunableKernel` with its Table-III-style
  search space.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hw import GpuSpec, TpuSpec, dtype_bytes
from repro.core.mix import InstructionMix
from repro.core.occupancy import (CudaOccupancy, CudaOccupancyBatch,
                                  TpuOccupancyBatch, cuda_occupancy,
                                  cuda_occupancy_batch, tpu_occupancy,
                                  tpu_occupancy_batch)
from repro.core.predict import cuda_eq6_time
from repro.core.autotuner import KernelStaticInfo

__all__ = ["cdiv", "default_interpret", "round_up", "block_info",
           "LANE", "tile_legal",
           "BatchStaticInfo", "block_info_batch",
           "CudaStaticInfo", "cuda_info",
           "CudaBatchStaticInfo", "cuda_info_batch",
           "pick_divisor_candidates", "require_tiling", "require_shape"]


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, m: int) -> int:
    return cdiv(x, m) * m


def default_interpret() -> bool:
    """Interpret on anything that is not a real TPU backend."""
    return jax.default_backend() != "tpu"


# Mosaic lays a block's trailing two dims out in (sublane, lane) tiles
# of 8 rows x 128 lanes of 32-bit words; narrower dtypes pack more rows
# into one tile (16 for bf16).
LANE = 128


def pick_divisor_candidates(n: int, candidates: Sequence[int]) -> tuple:
    """Keep candidates that divide n (BlockSpec-exact tiling), plus n
    itself when none of them is a multiple of `LANE`: the full extent
    is a legal TPU block in any position, so the space never lacks a
    block that compiles."""
    vals = tuple(c for c in candidates if c <= n and n % c == 0)
    if n not in vals and not any(v % LANE == 0 for v in vals):
        vals += (n,)
    return vals


def tile_legal(block: Sequence, array: Sequence, dtype):
    """The TPU block rule for one operand: the block's last dim is a
    multiple of `LANE` or the array's extent, and its second-to-last a
    multiple of the dtype's sublane count (8 for f32, 16 for bf16) or
    the array's extent.  Dims may be ints or (N,) arrays; returns a
    bool or an (N,) mask."""
    last = np.asarray(block[-1])
    ok = (last % LANE == 0) | (last == array[-1])
    if len(block) >= 2:
        sub = max(8, 32 // dtype_bytes(dtype))
        second = np.asarray(block[-2])
        ok = ok & ((second % sub == 0) | (second == array[-2]))
    return ok


def _tiles_legal(blocks, shapes, dtypes):
    """AND of `tile_legal` over every operand (True if no shapes)."""
    ok = np.asarray(True)
    for b, a, d in zip(blocks, shapes or (), dtypes):
        ok = ok & tile_legal(b, a, d)
    return ok


def require_tiling(kernel: str, shape: "dict", block: "dict") -> None:
    """ValueError when a launch block fails to tile its dimension.

    ``shape`` and ``block`` are same-length mappings pairing each
    dimension with its block size, in order.  These guard *user input*
    at trace time, so they must be real exceptions — a bare ``assert``
    vanishes under ``python -O``.
    """
    bad = [(dim, n, bname, b)
           for (dim, n), (bname, b) in zip(shape.items(), block.items())
           if n % b]
    if bad:
        detail = "; ".join(f"{bname}={b} does not divide {dim}={n}"
                           for dim, n, bname, b in bad)
        raise ValueError(
            f"{kernel}: shape {tuple(shape.values())} is not tileable by "
            f"block {dict(block)}: {detail}")


def require_shape(kernel: str, name: str, got: tuple, want: tuple) -> None:
    """ValueError (not assert) when an operand shape disagrees."""
    if tuple(got) != tuple(want):
        raise ValueError(f"{kernel}: {name} has shape {tuple(got)}, "
                         f"expected {tuple(want)}")


def block_info(*,
               in_blocks: Sequence[tuple],
               out_blocks: Sequence[tuple],
               in_dtypes: Sequence,
               out_dtypes: Sequence,
               flops_per_step: float,
               vpu_per_step: float = 0.0,
               trans_per_step: float = 0.0,
               grid_steps: int = 1,
               scratch_bytes: int = 0,
               mix_scale: float | None = None,
               ctrl_ops: float | None = None,
               in_shapes: Sequence[tuple] | None = None,
               out_shapes: Sequence[tuple] | None = None,
               spec: TpuSpec | None = None) -> KernelStaticInfo:
    """Analytic KernelStaticInfo from block shapes + per-step op counts.

    ``mix_scale`` defaults to ``grid_steps`` (total work = per-step work
    times the number of grid steps).  ``ctrl_ops`` overrides the
    control-op count (default: one per grid step) — kernels with an
    unroll axis amortize loop control across unrolled iterations.
    ``in_shapes`` / ``out_shapes`` are the full arrays each block
    tiles; given, a block that breaks the TPU block rule
    (`tile_legal`) makes the configuration infeasible.
    ``spec=None`` analyzes for the process-default target
    (`repro.core.target.default_target`).
    """
    in_bytes = [int(np.prod(b)) * dtype_bytes(d)
                for b, d in zip(in_blocks, in_dtypes)]
    out_bytes = [int(np.prod(b)) * dtype_bytes(d)
                 for b, d in zip(out_blocks, out_dtypes)]
    occ = tpu_occupancy(in_bytes, out_bytes, flops_per_step,
                        grid_steps=grid_steps,
                        scratch_bytes=scratch_bytes,
                        block_shapes=list(in_blocks) + list(out_blocks),
                        spec=spec)
    scale = grid_steps if mix_scale is None else mix_scale
    per_step_bytes = float(sum(in_bytes) + sum(out_bytes))
    mix = InstructionMix(
        mxu_flops=flops_per_step * scale,
        vpu_flops=vpu_per_step * scale,
        trans_flops=trans_per_step * scale,
        hbm_bytes=per_step_bytes * scale,
        vmem_bytes=per_step_bytes * scale,
        mem_ops=(per_step_bytes / 4.0) * scale,
        ctrl_ops=float(grid_steps if ctrl_ops is None else ctrl_ops),
        reg_ops=0.0,
    )
    legal = (_tiles_legal(in_blocks, in_shapes, in_dtypes)
             & _tiles_legal(out_blocks, out_shapes, out_dtypes))
    return KernelStaticInfo(mix=mix, occupancy=occ, tiles_legal=bool(legal))


@dataclasses.dataclass(frozen=True)
class BatchStaticInfo:
    """Struct-of-arrays `KernelStaticInfo` over N configurations.

    ``F`` is the (N, 7) feature matrix in `repro.core.predict`
    `features_matrix` column order (mxu, vpu, trans, hbm, vmem, ctrl,
    reg); ``occupancy`` carries the vectorized pipeline model.  Row
    ``i`` matches the scalar `block_info` for configuration ``i``
    exactly.  Feed ``F``/``pipe``/``feasible`` straight into
    `repro.core.predict.static_times_batch`.
    """

    F: np.ndarray                   # (N, 7) float64
    occupancy: TpuOccupancyBatch
    tiles_legal: np.ndarray         # (N,) bool: every block obeys the rule

    def __len__(self) -> int:
        return int(self.F.shape[0])

    @property
    def feasible(self) -> np.ndarray:
        return self.occupancy.fits_vmem & self.tiles_legal

    @property
    def pipe(self) -> np.ndarray:
        """Per-config pipeline floor: step time x grid steps."""
        return (self.occupancy.predicted_step_time
                * np.maximum(self.occupancy.grid_steps, 1))


def block_info_batch(*,
                     in_blocks: Sequence[tuple],
                     out_blocks: Sequence[tuple],
                     in_dtypes: Sequence,
                     out_dtypes: Sequence,
                     flops_per_step,
                     vpu_per_step=0.0,
                     trans_per_step=0.0,
                     grid_steps=1,
                     scratch_bytes=0,
                     mix_scale=None,
                     ctrl_ops=None,
                     in_shapes: Sequence[tuple] | None = None,
                     out_shapes: Sequence[tuple] | None = None,
                     spec: TpuSpec | None = None) -> BatchStaticInfo:
    """Vectorized `block_info`: one (N, 7) feature matrix + occupancy
    arrays for a whole config lattice in a single NumPy pass.

    Same contract as `block_info`, but block dims and per-step op
    counts may be (N,) arrays (typically `SearchSpace.enumerate_lattice`
    columns) broadcast against scalars.  No per-config Python objects
    are built — this is what makes cold full-space ranking array math
    instead of object churn.
    """
    def _elems(b):
        out = np.asarray(1, dtype=np.int64)
        for d in b:
            out = out * np.asarray(d, dtype=np.int64)
        return out

    in_bytes = [_elems(b) * dtype_bytes(d)
                for b, d in zip(in_blocks, in_dtypes)]
    out_bytes = [_elems(b) * dtype_bytes(d)
                 for b, d in zip(out_blocks, out_dtypes)]
    occ = tpu_occupancy_batch(in_bytes, out_bytes, flops_per_step,
                              grid_steps=grid_steps,
                              scratch_bytes=scratch_bytes,
                              block_shapes=list(in_blocks) + list(out_blocks),
                              spec=spec)
    n = len(occ)
    scale = grid_steps if mix_scale is None else mix_scale
    scale = np.asarray(scale, dtype=np.float64)
    per_step_bytes = np.asarray(sum(in_bytes) + sum(out_bytes),
                                dtype=np.float64)
    col = lambda a: np.broadcast_to(np.asarray(a, dtype=np.float64), (n,))
    F = np.column_stack([
        col(np.asarray(flops_per_step, dtype=np.float64) * scale),
        col(np.asarray(vpu_per_step, dtype=np.float64) * scale),
        col(np.asarray(trans_per_step, dtype=np.float64) * scale),
        col(per_step_bytes * scale),
        col(per_step_bytes * scale),
        col(np.asarray(grid_steps if ctrl_ops is None else ctrl_ops,
                       dtype=np.float64)),
        col(0.0),
    ])
    legal = (_tiles_legal(in_blocks, in_shapes, in_dtypes)
             & _tiles_legal(out_blocks, out_shapes, out_dtypes))
    return BatchStaticInfo(F=F, occupancy=occ,
                           tiles_legal=np.broadcast_to(legal, (n,)))


# ---------------------------------------------------------------------------
# CUDA static info (the faithful paper model behind GpuSpec targets)
# ---------------------------------------------------------------------------

# Occupancy floor when turning the Eq. 6 serial estimate into a launch-
# configuration cost: infeasible configs (occ == 0) are cut by the
# feasibility mask, so this only guards the division itself.
_CUDA_OCC_FLOOR = 1e-6


def _cuda_serial_seconds(o_fl, o_mem, o_ctrl, o_reg, gpu: GpuSpec):
    """Eq. 6 cycles at the core clock, as seconds (scalar or (N,))."""
    return cuda_eq6_time(o_fl, o_mem, o_ctrl, o_reg, gpu) \
        / (gpu.gpu_clock_mhz * 1e6)


@dataclasses.dataclass(frozen=True)
class CudaStaticInfo:
    """`KernelStaticInfo` analogue for one CUDA launch configuration.

    Duck-typed for `repro.core.predict.static_times_batch`: carries a
    ``mix`` (the Eq. 6 instruction classes on the shared feature
    columns, matching `default_cuda_model`), a ``feasible()`` cut
    (illegal launches: zero resident blocks, or a block wider than the
    chip's thread limit), and an ``occupancy`` view exposing
    ``predicted_step_time`` / ``grid_steps`` — the Eq. 6 serial time
    stretched by the occupancy deficit, which is the ranking signal
    across thread-block candidates (Table VII: prefer max occupancy).
    """

    mix: InstructionMix
    cuda: CudaOccupancy
    threads: int
    predicted_step_time: float
    thread_cap: int             # chip T_B^cc the launch must respect
    grid_steps: int = 1

    @property
    def occupancy(self):
        # static_times_batch reads .occupancy.predicted_step_time and
        # .occupancy.grid_steps; this object carries both itself.
        return self

    def feasible(self) -> bool:
        return bool(self.cuda.active_blocks > 0
                    and 0 < self.threads <= self.thread_cap)


def cuda_info(threads, *,
              regs_per_thread: int,
              shmem_per_block: int,
              o_fl: float = 1.0,
              o_mem: float = 1.0,
              o_ctrl: float = 1.0,
              o_reg: float = 1.0,
              spec: GpuSpec) -> CudaStaticInfo:
    """Analytic `CudaStaticInfo` for one (T^u, R^u, S^u) configuration.

    The CUDA counterpart of :func:`block_info`: instruction-class
    counts (whole-kernel O_fl / O_mem / O_ctrl / O_reg) plus the
    paper's occupancy calculation, no compilation, no execution.
    """
    t = int(threads)
    occ = cuda_occupancy(t, regs_per_thread, shmem_per_block, spec)
    serial = _cuda_serial_seconds(o_fl, o_mem, o_ctrl, o_reg, spec)
    step = serial / max(occ.occupancy, _CUDA_OCC_FLOOR)
    mix = InstructionMix(mxu_flops=o_fl, hbm_bytes=o_mem,
                         ctrl_ops=o_ctrl, reg_ops=o_reg)
    return CudaStaticInfo(mix=mix, cuda=occ, threads=t,
                          predicted_step_time=step,
                          thread_cap=spec.threads_per_block)


@dataclasses.dataclass(frozen=True)
class CudaBatchStaticInfo:
    """Struct-of-arrays `CudaStaticInfo` over N thread-block candidates.

    Same field contract `rank_space` consumes from `BatchStaticInfo`:
    ``F`` is the (N, 7) feature matrix in `features_matrix` column
    order (CUDA classes on the mapped columns), ``pipe`` the per-config
    occupancy-stretched Eq. 6 floor, ``feasible`` the legality mask.
    Row ``i`` matches the scalar :func:`cuda_info` exactly.
    """

    F: np.ndarray                   # (N, 7) float64
    occupancy: CudaOccupancyBatch
    pipe: np.ndarray                # (N,) float64
    feasible: np.ndarray            # (N,) bool

    def __len__(self) -> int:
        return int(self.F.shape[0])


def cuda_info_batch(threads, *,
                    regs_per_thread,
                    shmem_per_block,
                    o_fl: float = 1.0,
                    o_mem: float = 1.0,
                    o_ctrl: float = 1.0,
                    o_reg: float = 1.0,
                    spec: GpuSpec) -> CudaBatchStaticInfo:
    """Vectorized :func:`cuda_info` over a whole thread-size lattice.

    ``threads`` (and, if per-config, ``regs_per_thread`` /
    ``shmem_per_block``) are (N,) arrays — typically the ``threads``
    column of `SearchSpace.enumerate_lattice`; the occupancy pass is
    one `cuda_occupancy_batch` call and the instruction-class counts
    broadcast, so ranking a GPU space is array math end to end, just
    like the TPU path.
    """
    t = np.atleast_1d(np.asarray(threads, dtype=np.int64))
    occ = cuda_occupancy_batch(t, regs_per_thread, shmem_per_block, spec)
    n = len(occ)
    serial = _cuda_serial_seconds(float(o_fl), float(o_mem), float(o_ctrl),
                                  float(o_reg), spec)
    pipe = serial / np.maximum(occ.occupancy, _CUDA_OCC_FLOOR)
    tb = np.broadcast_to(t, (n,))
    feasible = (occ.active_blocks > 0) & (tb > 0) \
        & (tb <= spec.threads_per_block)
    col = lambda a: np.broadcast_to(np.asarray(a, dtype=np.float64), (n,))
    F = np.column_stack([col(o_fl), col(0.0), col(0.0), col(o_mem),
                         col(0.0), col(o_ctrl), col(o_reg)])
    return CudaBatchStaticInfo(F=F, occupancy=occ, pipe=pipe,
                               feasible=feasible)
