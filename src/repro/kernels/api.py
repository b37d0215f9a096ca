"""`@tuned_kernel` — one declarative registration for the whole stack.

The paper's front door is an *annotation*: an Orio user declares a
tunable region plus its parameter space and the static analyzer does
the rest.  This module is that posture made structural for Pallas
kernels.  One declaration site::

    @tuned_kernel(
        "stencil2d",
        space={"by": divisors("y", (8, 16, 32, 64, 128, 256))},
        signature=lambda u, **_: dict(y=u.shape[0], x=u.shape[1],
                                      dtype=str(u.dtype)),
        static_info=_stencil2d_analysis,     # (p, *, y, x, dtype) -> kwargs
        make_inputs=_stencil2d_inputs,
        reference=stencil2d_ref,
        pretune=(dict(y=512, x=512, dtype="float32"), ...),
    )
    def stencil2d_pallas(u, *, by=32, interpret=None): ...

derives everything the six in-tree kernels used to wire by hand across
four layers:

* the **trace-time dispatch wrapper** (`KernelSpec.op`, re-exported as
  ``repro.kernels.ops.<kernel_id>``): extracts the signature from the
  call arguments, resolves launch params through the tuning database
  for the active hardware target, falls back to largest-divisor
  defaults if dispatch fails;
* the **dispatch registry entry** (`TuningProblem` factory +
  signature normalization) consumed by
  `repro.tuning_cache.lookup_or_tune`;
* **scalar and batched static analysis** from one array-agnostic
  ``static_info`` builder — the same code path produces the
  `KernelStaticInfo` object and the struct-of-arrays
  `BatchStaticInfo`, so batch/scalar parity holds by construction;
* **`TunableKernel` construction** (`KernelSpec.tunable`) for the full
  `KernelTuner` (static / hybrid / empirical modes);
* the **largest-divisor fallback params** and the kernel's entries in
  the shipped per-target pre-tuned grid (``pretune=``).

``space`` also accepts an Orio-style ``PerfTuning`` annotation string
(paper Fig. 3); see `repro.core.annotations.parse_tuning_spec`.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import inspect
import itertools
import logging
import threading
from typing import (Any, Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple, Union)

import numpy as np

from repro import obs, tuning_cache
from repro.tuning_cache.binder import SigBinder, compile_binder, schema_of
from repro.core.annotations import parse_tuning_spec
from repro.core.autotuner import KernelStaticInfo, TunableKernel
from repro.core.hw import GpuSpec
from repro.core.search import Constraint, Params, SearchSpace
from repro.core.target import default_target
from repro.kernels.common import (BatchStaticInfo, block_info,
                                  block_info_batch, cuda_info,
                                  cuda_info_batch,
                                  pick_divisor_candidates)
from repro.kernels.variants import (KernelVariant, VARIANT_AXIS,
                                    check_variant_schema, joint_space,
                                    joint_static_info,
                                    joint_static_info_batch,
                                    variants_fingerprint)

__all__ = [
    "KernelSpec", "tuned_kernel", "divisors", "Divisors",
    "CudaProfile", "cuda_profile", "KernelVariant",
    "register_variant", "unregister_variant",
    "get_spec", "registered_kernels", "unregister",
    "reset_dispatch_failure_log",
    "dispatch_stats", "reset_dispatch_stats", "collect_dispatches",
]

_log = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Axis declarations
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Divisors:
    """A tunable axis whose candidates must tile a signature dimension.

    At problem-construction time the candidate list is filtered to the
    values that divide ``signature[dim]`` (BlockSpec-exact tiling), and
    the dimension itself joins them when none is lane-aligned
    (`kernels.common.pick_divisor_candidates`).  The derived fallback
    param is the largest surviving candidate — the same "largest
    divisor" rule the hand-written ops used.
    """

    dim: str
    candidates: Tuple[int, ...]

    def materialize(self, signature: Mapping[str, Any]) -> Tuple[int, ...]:
        if self.dim not in signature:
            raise KeyError(
                f"axis is tied to signature dim {self.dim!r}, which the "
                f"signature {dict(signature)} does not carry")
        return pick_divisor_candidates(int(signature[self.dim]),
                                       self.candidates)

    def fallback(self, signature: Mapping[str, Any]) -> int:
        return max(self.materialize(signature))


def divisors(dim: str, candidates: Sequence[int]) -> Divisors:
    """Declare an axis of block sizes that must divide ``dim``."""
    return Divisors(dim=dim, candidates=tuple(candidates))


class _Literal:
    """A fixed candidate tuple (signature-independent axis)."""

    __slots__ = ("values",)

    def __init__(self, values: Sequence[Any]):
        self.values = tuple(values)
        if not self.values:
            raise ValueError("literal axis needs at least one candidate")

    def materialize(self, signature: Mapping[str, Any]) -> Tuple[Any, ...]:
        return self.values

    def fallback(self, signature: Mapping[str, Any]) -> Any:
        return self.values[len(self.values) // 2]


def _coerce_space(kernel_id: str, space) -> Dict[str, Any]:
    """Accept {name: Divisors | sequence} or an Orio annotation string."""
    if isinstance(space, str):
        space = {name: tuple(vals)
                 for name, vals in parse_tuning_spec(space).axes.items()}
    if not isinstance(space, Mapping) or not space:
        raise ValueError(
            f"@tuned_kernel({kernel_id!r}): space must declare at least "
            f"one tunable axis (a dict of axes or a PerfTuning "
            f"annotation string), got {space!r}")
    out: Dict[str, Any] = {}
    for name, axis in space.items():
        if isinstance(axis, Divisors):
            out[name] = axis
        elif isinstance(axis, (tuple, list)):
            out[name] = _Literal(axis)
        else:
            raise ValueError(
                f"@tuned_kernel({kernel_id!r}): axis {name!r} must be "
                f"divisors(...) or a sequence of candidates, "
                f"got {axis!r}")
    return out


# ---------------------------------------------------------------------------
# CUDA-side declaration (GpuSpec targets)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CudaProfile:
    """What the faithful CUDA models need to know about one kernel.

    The paper's static analysis reads three things off a compiled CUDA
    kernel: its register pressure R^u (per compute capability — the
    same source compiles to different pressures per chip generation,
    which is why Table VII prints one R^u per column), its shared
    memory per block S^u, and its instruction-class counts (Eq. 6's
    O_fl / O_mem / O_ctrl / O_reg).  A Pallas reproduction has no CUDA
    compiler to ask, so the declaration carries them:

    * ``regs`` — R^u as a flat int, or a ``{family: R^u}`` mapping
      keyed by `GpuSpec.family` ('Fermi' / 'Kepler' / 'Maxwell');
      a missing family falls back to the ``'default'`` key, then to
      the mapping's max (conservative pressure).
    * ``shmem_per_block`` — S^u bytes, an int or a
      ``(**signature) -> int`` callable.
    * ``workload`` — ``(**signature) -> {o_fl, o_mem, o_ctrl, o_reg}``
      whole-kernel class counts; omitted counts default to 1.0 (the
      occupancy term alone then drives the ranking, which is exactly
      Table VII's rule: prefer max occupancy).
    * ``threads`` — candidate T^u override; default: every warp
      multiple up to the chip's block limit, the same lattice
      `repro.core.occupancy.suggest_cuda_params` sweeps.
    """

    regs: Union[int, Mapping[str, int]] = 32
    shmem_per_block: Union[int, Callable[..., int]] = 0
    workload: Optional[Callable[..., Mapping[str, float]]] = None
    threads: Optional[Tuple[int, ...]] = None

    _COUNTS = ("o_fl", "o_mem", "o_ctrl", "o_reg")

    def regs_for(self, gpu: GpuSpec) -> int:
        if isinstance(self.regs, Mapping):
            v = self.regs.get(gpu.family, self.regs.get("default"))
            return int(v if v is not None else max(self.regs.values()))
        return int(self.regs)

    def shmem_for(self, **signature) -> int:
        if callable(self.shmem_per_block):
            return int(self.shmem_per_block(**signature))
        return int(self.shmem_per_block)

    def counts(self, **signature) -> Dict[str, float]:
        out = dict.fromkeys(self._COUNTS, 1.0)
        if self.workload is not None:
            declared = dict(self.workload(**signature))
            unknown = set(declared) - set(self._COUNTS)
            if unknown:
                raise ValueError(
                    f"cuda workload returned unknown instruction "
                    f"classes {sorted(unknown)}; expected a subset of "
                    f"{list(self._COUNTS)}")
            out.update({k: float(v) for k, v in declared.items()})
        return out

    def thread_candidates(self, gpu: GpuSpec) -> Tuple[int, ...]:
        if self.threads is not None:
            return self.threads
        return tuple(range(gpu.warp_size, gpu.threads_per_block + 1,
                           gpu.warp_size))


def cuda_profile(**kwargs) -> CudaProfile:
    """Declare a kernel's CUDA-side analysis inputs (``cuda=`` of
    `tuned_kernel`); see :class:`CudaProfile` for the fields."""
    return CudaProfile(**kwargs)


# The profile used when a kernel declares no ``cuda=``: moderate
# register pressure, no shared memory, unit instruction counts — every
# `@tuned_kernel` dispatches under a GpuSpec target out of the box, and
# a declaration refines the numbers.
_GENERIC_CUDA = CudaProfile()


# ---------------------------------------------------------------------------
# Dispatch accounting + graph enumeration (shared by every op wrapper)
# ---------------------------------------------------------------------------


# frozen: the frozen-table probe answered; live: the memo/db resolve
# answered in full; fallback: largest-divisor params filled gaps;
# explicit: the caller passed tuned_params=; collected: recorded by
# collect_dispatches()
_TIERS = ("frozen", "live", "fallback", "explicit", "collected")


def dispatch_stats() -> Dict[str, int]:
    """Counters of how op dispatches resolved since the last reset:
    ``frozen`` / ``live`` / ``fallback`` / ``explicit`` (+ their sum
    ``total``) and ``collected`` (enumeration-only dispatches recorded
    under `collect_dispatches`, excluded from ``total``).  A view of the
    ``dispatch.<tier>`` counters of `repro.obs`."""
    c = obs.counters()
    d = {k: c.get(f"dispatch.{k}", 0) for k in _TIERS}
    d["total"] = d["frozen"] + d["live"] + d["fallback"] + d["explicit"]
    return d


def reset_dispatch_stats() -> None:
    obs.reset_counters("dispatch.")


_COLLECT: "contextvars.ContextVar[Optional[List[Tuple[str, Dict]]]]" = \
    contextvars.ContextVar("repro_collect_dispatches", default=None)


@contextlib.contextmanager
def collect_dispatches():
    """Record every op dispatch as ``(kernel_id, signature)`` instead of
    touching the tuning database.

    While active, op wrappers append the extracted signature to the
    yielded list and launch with fallback params — no frozen probe, no
    db lookup, no tuning.  Run a model forward pass under
    ``jax.eval_shape`` inside this context and the list is *exactly*
    the (kernel, shape, dtype) instance set runtime dispatch will ask
    for — `GraphTuner.tune_config` builds its pretune set this way, so
    enumeration can never drift from dispatch.
    """
    sink: List[Tuple[str, Dict]] = []
    tok = _COLLECT.set(sink)
    try:
        yield sink
    finally:
        _COLLECT.reset(tok)


# ---------------------------------------------------------------------------
# Dispatch-failure log (shared by every generated op wrapper)
# ---------------------------------------------------------------------------

# kernel_ids whose dispatch failure already produced a full traceback; a
# persistently broken registry entry logs once per process, not once per
# trace.  Guarded by a lock (ops dispatch from model threads) and
# cleared by `reset_dispatch_failure_log` / `clear_dispatch_memo`.
_logged_dispatch_failures: set = set()
_failures_lock = threading.Lock()


def reset_dispatch_failure_log() -> None:
    """Forget which kernels already logged a dispatch failure (tests)."""
    with _failures_lock:
        _logged_dispatch_failures.clear()


tuning_cache.registry.on_dispatch_memo_clear(reset_dispatch_failure_log)


def _resolve(kernel_id: str, signature: Dict) -> Dict:
    """Trace-time launch-config lookup for the active hardware target;
    never raises (returns {} on failure so the fallback params apply)."""
    try:
        return tuning_cache.lookup_or_tune(
            kernel_id, spec=default_target(), **signature)
    except Exception:
        with _failures_lock:
            first = kernel_id not in _logged_dispatch_failures
            if first:
                _logged_dispatch_failures.add(kernel_id)
        if first:
            _log.exception("tuning-cache dispatch failed for %s %s; "
                           "using fallback defaults (further failures "
                           "for this kernel log at DEBUG)",
                           kernel_id, signature)
        else:
            _log.debug("tuning-cache dispatch failed for %s %s; "
                       "using fallback defaults", kernel_id, signature)
        return {}


# ---------------------------------------------------------------------------
# KernelSpec
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class KernelSpec:
    """Everything the tuning stack derives from one `@tuned_kernel`.

    Contract of the declared pieces (DESIGN.md §10):

    * ``fn(*arrays, **launch_params)`` — the Pallas entry point; launch
      params are keywords named exactly like the space axes.
    * ``extract_signature(*args, **kwargs) -> dict`` — maps a concrete
      call to the shape/dtype signature.  Works on tracers (shapes and
      dtypes only).
    * ``analysis(p, **signature) -> dict`` — array-agnostic static
      analyzer: ``p`` maps axis names to scalars (one config) or (N,)
      arrays (a whole lattice); the return value is splatted into
      `repro.kernels.common.block_info` / `block_info_batch`.  Its
      keyword parameters *are* the signature schema: required names
      and defaults are taken from ``inspect.signature(analysis)``.
    * ``make_inputs(key, **signature) -> tuple`` — random inputs for
      empirical/hybrid tuning (optional; static-only kernels may omit
      it).
    * ``reference`` — the pure-jnp oracle (optional).
    * ``pretune`` — signatures swept into the shipped per-target
      pre-tuned databases by ``python -m repro.tuning_cache pretune``.
    * ``cuda`` — optional :class:`CudaProfile`: register pressure,
      shared memory, and Eq. 6 instruction counts for `GpuSpec`
      targets.  Omitted, a generic profile applies (see
      ``_GENERIC_CUDA``), so every declared kernel is dispatchable
      under a CUDA target either way.
    """

    kernel_id: str
    fn: Callable[..., Any]
    space: Dict[str, Any]
    extract_signature: Callable[..., Dict[str, Any]]
    analysis: Callable[..., Dict[str, Any]]
    fallback: Optional[Callable[..., Dict[str, Any]]] = None
    make_inputs: Optional[Callable[..., tuple]] = None
    reference: Optional[Callable[..., Any]] = None
    pretune: Tuple[Dict[str, Any], ...] = ()
    cuda: Optional[CudaProfile] = None
    # Cost-model tier this kernel's default dispatch ranks under: None
    # (the process default, see `tuning_cache.set_default_model`) or a
    # kind from `tuning_cache.MODEL_KINDS` — "eq6" (Eq. 6 CPI-linear)
    # or "pipeline" (latency-table scoreboard reranker, DESIGN.md §16).
    model: Optional[str] = None
    # Optional per-config instruction stream for the pipeline tier:
    # ``schedule(p, **signature)`` returns (class, units[, dep]) rows
    # (or an `repro.core.pipeline.InstructionStream`).  Omitted, the
    # stream is synthesized from the kernel's 7-feature mix.
    schedule: Optional[Callable[..., Any]] = None
    # Feasibility constraints over the declared axes: a sequence of
    # `repro.core.search.Constraint` (or bare columns->mask callables),
    # or a single ``(**signature) -> sequence`` factory for constraints
    # that close over signature dims (e.g. "bm must divide m").  They
    # restrict the *TPU block space*; the CUDA threads space is its own
    # lattice and ignores them.
    constraints: Any = None
    # preferred rank_space streaming chunk (None: DEFAULT_CHUNK)
    chunk_size: Optional[int] = None
    # Additional implementations of this logical op (a sequence of
    # `KernelVariant`).  When any are declared — or added later via
    # `register_variant` — the decorated fn/space/analysis/constraints
    # become the *primary* variant (id ``primary_variant``, default
    # "primary"), ``"variant"`` becomes a joint-space axis, and every
    # cache record stores the winning implementation id (DESIGN.md §15).
    variants: Any = None
    primary_variant: Optional[str] = None

    def __post_init__(self):
        if not self.kernel_id or not isinstance(self.kernel_id, str):
            raise ValueError(f"kernel_id must be a non-empty string, "
                             f"got {self.kernel_id!r}")
        if self.model is not None:
            kinds = tuning_cache.MODEL_KINDS
            if self.model not in kinds:
                raise ValueError(
                    f"@tuned_kernel({self.kernel_id!r}): model must be "
                    f"one of {kinds}, got {self.model!r}")
        self.space = _coerce_space(self.kernel_id, self.space)
        if VARIANT_AXIS in self.space:
            raise ValueError(
                f"@tuned_kernel({self.kernel_id!r}): axis {VARIANT_AXIS!r} "
                f"is reserved for the joint variant axis")
        # The analysis builder's keyword params are the signature
        # schema — same binding semantics the old per-kernel factories
        # got from inspect.signature(factory).
        params = list(inspect.signature(self.analysis).parameters.values())
        if not params:
            raise ValueError(
                f"@tuned_kernel({self.kernel_id!r}): static_info builder "
                f"must take (params, **signature)")
        self._sig_schema = inspect.Signature(params[1:])
        self._sig_names = tuple(self._sig_schema.parameters)
        # Declaration-time normalization: the schema compiles once into
        # a canonical key builder (repro.tuning_cache.binder), so warm
        # dispatch never pays inspect.bind or a per-call sort.  None
        # for exotic schemas (*args/**kwargs) — those fall back to the
        # inspect path and are excluded from the frozen tier.
        self._binder = compile_binder(schema_of(params[1:]))
        self.pretune = tuple(dict(s) for s in self.pretune)
        self._op = None
        self._fn_kw = None
        self._fallback_cache: Dict[Tuple, Dict[str, Any]] = {}
        self._axis_names = frozenset(self.space)
        self._primary_id = self.primary_variant or "primary"
        self._variants: Optional[Dict[str, KernelVariant]] = None
        extra = tuple(self.variants or ())
        if extra or self.primary_variant is not None:
            self._variants = {self._primary_id: self._primary_as_variant()}
            for v in extra:
                self.add_variant(v, _notify=False)
        self.variants = None     # consumed into _variants; don't alias

    # -- variant set --------------------------------------------------------
    def _primary_as_variant(self) -> KernelVariant:
        return KernelVariant(variant_id=self._primary_id, fn=self.fn,
                             space=self.space, analysis=self.analysis,
                             constraints=self.constraints)

    def variant_ids(self) -> Tuple[str, ...]:
        """Registered implementation ids, insertion-ordered (empty for a
        single-implementation kernel)."""
        return tuple(self._variants) if self._variants is not None else ()

    def add_variant(self, variant: KernelVariant, *,
                    _notify: bool = True) -> None:
        """Register another implementation of this logical op.

        Converts a single-implementation spec to variant dispatch (the
        decorated fn becomes the primary variant) and invalidates this
        kernel's dispatch state — frozen tables thaw and its live memo
        shard entry drops, because every existing record now answers
        for a different (smaller) variant set.
        """
        if not isinstance(variant, KernelVariant):
            raise TypeError(f"add_variant wants a KernelVariant, "
                            f"got {variant!r}")
        v = dataclasses.replace(
            variant,
            space=_coerce_space(f"{self.kernel_id}/{variant.variant_id}",
                                variant.space))
        check_variant_schema(self.kernel_id, self._sig_names, v)
        cur = self._variants
        if cur is None:
            cur = {self._primary_id: self._primary_as_variant()}
        if v.variant_id in cur:
            raise ValueError(
                f"@tuned_kernel({self.kernel_id!r}): variant "
                f"{v.variant_id!r} is already registered")
        new = dict(cur)
        new[v.variant_id] = v
        # one atomic publish: racing dispatches see old set or new set
        self._variants = new
        self._fallback_cache = {}
        if _notify:
            tuning_cache.registry.invalidate_kernel(self.kernel_id)

    def remove_variant(self, variant_id: str) -> "KernelVariant":
        """Unregister an implementation (the primary cannot be removed —
        it backs the fallback path).  Invalidates dispatch state like
        `add_variant`; the spec stays in variant mode even with only
        the primary left, because its records carry a variant id.
        Returns the removed variant (so callers can re-register it)."""
        cur = self._variants
        if cur is None or variant_id not in cur:
            raise KeyError(
                f"@tuned_kernel({self.kernel_id!r}) has no variant "
                f"{variant_id!r}; registered: {list(cur or ())}")
        if variant_id == self._primary_id:
            raise ValueError(
                f"@tuned_kernel({self.kernel_id!r}): cannot remove the "
                f"primary variant {variant_id!r}")
        new = dict(cur)
        removed = new.pop(variant_id)
        self._variants = new
        self._fallback_cache = {}
        tuning_cache.registry.invalidate_kernel(self.kernel_id)
        return removed

    def key_extras(self) -> Dict[str, Any]:
        """Extra cache-key signature entries this spec requires.

        Variant mode contributes ``{"variants": <structural digest>}``
        so records ranked under one variant set never satisfy lookups
        (or frozen-table builds) under another.  The registry folds
        these into `make_key` for every tier — dispatch and freeze
        agree by construction.
        """
        if self._variants is None:
            return {}
        return {"variants": variants_fingerprint(self._variants)}

    # -- signature plumbing -------------------------------------------------
    def sig_binder(self) -> Optional[SigBinder]:
        """The declaration-compiled signature key builder (the registry
        and the frozen dispatch tier consume this)."""
        return self._binder

    def normalize(self, signature: Mapping[str, Any]) -> Dict[str, Any]:
        """Bind a partial signature through the declared defaults.

        Keys must be identical no matter how the signature was spelled:
        ``tune --sig m=1024 ...`` (dtype omitted, default applies) has
        to produce the same record as the op passing ``dtype='float32'``
        explicitly, or CLI-produced databases would be permanent cache
        misses at trace time.  Raises TypeError on missing or unknown
        keys, like the old factory binding did.
        """
        b = self._binder
        if b is not None:
            out = b.normalized(signature)
            if out is not None:
                return out
            # invalid spelling: fall through for the proper TypeError
        ba = self._sig_schema.bind(**signature)
        ba.apply_defaults()
        return dict(ba.arguments)

    # -- static analysis (scalar and batched, from one builder) -------------
    def static_info(self, params: Params, **signature) -> KernelStaticInfo:
        sig = self.normalize(signature)
        if self._variants is not None:
            p = dict(params)
            p.setdefault(VARIANT_AXIS, self._primary_id)
            return joint_static_info(self._variants, p, sig)
        return block_info(**self.analysis(params, **sig))

    def static_info_batch(self, cols: Mapping[str, np.ndarray],
                          **signature) -> BatchStaticInfo:
        sig = self.normalize(signature)
        if self._variants is not None:
            return joint_static_info_batch(self._variants, cols, sig)
        return block_info_batch(**self.analysis(cols, **sig))

    # -- derived artifacts ---------------------------------------------------
    def _materialize_constraints(self,
                                 sig: Dict[str, Any]) -> Tuple[Any, ...]:
        cons = self.constraints
        if cons is None:
            return ()
        if callable(cons) and not isinstance(cons, Constraint):
            cons = cons(**sig)
        return tuple(cons or ())

    def search_space(self, **signature) -> SearchSpace:
        sig = self.normalize(signature)
        if self._variants is not None:
            return joint_space(self._variants, sig)
        return SearchSpace({name: axis.materialize(sig)
                            for name, axis in self.space.items()},
                           constraints=self._materialize_constraints(sig))

    def _fallback_over(self, tag: Optional[str], space: Mapping[str, Any],
                       analyze: Callable[[Dict[str, Any]], KernelStaticInfo],
                       sig: Dict[str, Any]) -> Dict[str, Any]:
        """Largest-divisor fallback over one axis set, backed off
        (largest block first) until ``analyze`` reports it feasible
        (fits VMEM, every block legal).  Memoized per (variant tag,
        signature)."""
        try:
            memo_key = (tag, tuple(sorted(sig.items())))
            hit = self._fallback_cache.get(memo_key)
            if hit is not None:
                return dict(hit)
        except TypeError:               # unhashable signature value
            memo_key = None
        cands = {name: axis.materialize(sig)
                 for name, axis in space.items()}
        numeric = all(isinstance(v, (int, np.integer))
                      for vals in cands.values() for v in vals)
        if not numeric:                  # literal axes: per-axis defaults
            out = {name: axis.fallback(sig)
                   for name, axis in space.items()}
        else:
            cands = {name: tuple(sorted(set(v)))
                     for name, v in cands.items()}
            idx = {name: len(v) - 1 for name, v in cands.items()}
            current = lambda: {name: cands[name][i]
                               for name, i in idx.items()}
            try:
                while not analyze(current()).feasible():
                    movable = [n for n in idx if idx[n] > 0]
                    if not movable:
                        # backing off one axis can pass through blocks
                        # the TPU block rule refuses: take the first
                        # feasible config, largest first, if any
                        for ix in itertools.product(
                                *(range(len(v) - 1, -1, -1)
                                  for v in cands.values())):
                            idx = dict(zip(cands, ix))
                            if analyze(current()).feasible():
                                break
                        else:
                            idx = {name: len(v) - 1
                                   for name, v in cands.items()}
                        break
                    biggest = max(movable, key=lambda n: cands[n][idx[n]])
                    idx[biggest] -= 1
            except Exception:
                # analyzer unavailable: the plain largest-divisor rule
                # is still a valid tiling, just possibly large
                idx = {name: len(v) - 1 for name, v in cands.items()}
            out = current()
        if memo_key is not None:
            self._fallback_cache[memo_key] = dict(out)
        return out

    def _variant_fallback(self, var: KernelVariant,
                          sig: Dict[str, Any]) -> Dict[str, Any]:
        return self._fallback_over(
            var.variant_id, var.space,
            lambda p: block_info(**var.analysis(p, **sig)), sig)

    def fallback_params(self, **signature) -> Dict[str, Any]:
        """Launch params used when database dispatch is unavailable.

        Derived default: the largest dividing candidate per axis,
        backed off (largest block first) until the kernel's own static
        analysis says the working set fits VMEM — so the failure path
        can never emit a launch the chip rejects.  Memoized per
        signature; an explicit ``fallback=`` declaration overrides.
        Variant mode falls back to the *primary* implementation (its id
        rides along under ``"variant"``).
        """
        sig = self.normalize(signature)
        if self.fallback is not None:
            out = dict(self.fallback(**sig))
            if self._variants is not None:
                out.setdefault(VARIANT_AXIS, self._primary_id)
            return out
        if self._variants is not None:
            var = self._variants[self._primary_id]
            out = self._variant_fallback(var, sig)
            return {VARIANT_AXIS: self._primary_id, **out}
        return self._fallback_over(
            None, self.space,
            lambda p: block_info(**self.analysis(p, **sig)), sig)

    def problem(self, **signature) -> "tuning_cache.TuningProblem":
        """The dispatch-registry factory the stack used to hand-write.

        Family-polymorphic over the *active* target
        (`repro.core.target.default_target` — `lookup_or_tune` pins it
        to the spec the cache key was built for): a `TpuSpec` yields
        the declared Pallas block space with the VMEM-feasibility
        analyzers, a `GpuSpec` yields the CUDA thread-block space with
        the faithful Eqs. 1-5 occupancy + Eq. 6 feasibility/cost
        analyzers (threads/regs/shmem axes instead of VMEM blocks).
        """
        sig = self.normalize(signature)
        spec = default_target()
        if isinstance(spec, GpuSpec):
            return self._cuda_problem(spec, sig)
        return tuning_cache.TuningProblem(
            space=self.search_space(**sig),
            static_info=lambda p: self.static_info(p, **sig),
            static_info_batch=lambda c: self.static_info_batch(c, **sig),
            chunk_size=self.chunk_size,
            schedule=(lambda p, _sig=sig: self.schedule(p, **_sig))
                     if self.schedule is not None else None)

    def _cuda_problem(self, gpu: GpuSpec,
                      sig: Dict[str, Any]) -> "tuning_cache.TuningProblem":
        prof = self.cuda if self.cuda is not None else _GENERIC_CUDA
        kw = dict(regs_per_thread=prof.regs_for(gpu),
                  shmem_per_block=prof.shmem_for(**sig),
                  spec=gpu, **prof.counts(**sig))
        return tuning_cache.TuningProblem(
            space=SearchSpace({"threads": prof.thread_candidates(gpu)}),
            static_info=lambda p: cuda_info(p["threads"], **kw),
            static_info_batch=lambda c: cuda_info_batch(c["threads"], **kw))

    def _fn_keywords(self) -> frozenset:
        if self._fn_kw is None:
            ps = inspect.signature(self.fn).parameters.values()
            self._fn_kw = frozenset(
                p.name for p in ps
                if p.kind in (inspect.Parameter.POSITIONAL_OR_KEYWORD,
                              inspect.Parameter.KEYWORD_ONLY))
        return self._fn_kw

    def _launch(self, p: Optional[Mapping[str, Any]],
                sig: Dict[str, Any]) -> Tuple[Callable, Dict[str, Any], bool]:
        """Turn resolved params ``p`` into ``(fn, launch_kwargs,
        complete)`` — the implementation to call, the launch params to
        pass it, and whether dispatch covered every axis (False means
        the largest-divisor fallback filled gaps).  ``p=None`` forces
        the fallback path.  Computed per call, never captured at op
        creation, so a variant registered after the op exists routes
        immediately.
        """
        variants = self._variants
        if variants is None:
            names = self._axis_names
            launch = ({k: v for k, v in p.items() if k in names}
                      if p else {})
            complete = len(launch) == len(names)
            # dispatch failed or returned partial params: fill the
            # gaps with the feasible largest-divisor fallback
            if not complete:
                launch = {**self.fallback_params(**sig), **launch}
            return self.fn, launch, complete
        var = variants.get(p.get(VARIANT_AXIS)) if p else None
        if var is None:
            # no params, or a winner whose variant has since been
            # unregistered: primary-variant fallback
            fb = self.fallback_params(**sig)
            var = variants[fb[VARIANT_AXIS]]
            launch = {k: v for k, v in fb.items() if k in var.space}
            return var.fn, launch, False
        # joint winners carry the union axes (foreign ones pinned);
        # launch with the winning variant's own axes only
        launch = {k: v for k, v in p.items() if k in var.space}
        complete = len(launch) == len(var.space)
        if not complete:
            launch = {**self._variant_fallback(var, sig), **launch}
        return var.fn, launch, complete

    @property
    def op(self) -> Callable[..., Any]:
        """The trace-time dispatch wrapper (what ``ops.py`` re-exports).

        Resolves launch params through the tuning database for the
        active target on every trace; ``tuned_params`` injects a
        :class:`~repro.core.autotuner.TuningReport`'s best_params
        explicitly, bypassing the database.  If dispatch fails the
        largest-divisor fallback applies, so dispatch can never break a
        numerically-correct call.

        Under a `GpuSpec` target (an *analysis-only* backend: there is
        no CUDA executable to launch from jax_pallas) dispatch still
        records and returns the CUDA ``{"threads": ...}`` ranking, but
        none of those params name a Pallas axis — the wrapper then
        runs the Pallas body with the feasible fallback tiling, so a
        program stays numerically correct while its launch analysis is
        being done for a GPU.
        """
        if self._op is None:
            kernel_id = self.kernel_id
            registry = tuning_cache.registry
            count = obs.count
            # (frozen state, probe) pair published as ONE tuple: a
            # single attribute store is atomic under the GIL, so racing
            # dispatch threads can never pair a stale probe with a
            # fresh state.  Revalidated against registry._FROZEN by
            # identity on every call — thaw/re-freeze is picked up
            # without any lock on the hot path.
            cache = [(None, None)]

            def op(*args, tuned_params: Optional[Dict] = None, **kw):
                sig = self.extract_signature(*args, **kw)
                col = _COLLECT.get()
                if col is not None:
                    col.append((kernel_id, dict(sig)))
                    count("dispatch.collected")
                    fn, launch, _ = self._launch(None, sig)
                    with obs.scope(kernel_id):
                        return fn(*args, **launch, **kw)
                if tuned_params is not None:
                    count("dispatch.explicit")
                    fn, launch, _ = self._launch(tuned_params, sig)
                    with obs.scope(kernel_id):
                        return fn(*args, **launch, **kw)
                fz = registry._FROZEN
                state, probe = cache[0]
                if state is not fz:
                    probe = (fz.tables.get((kernel_id, "static"))
                             if fz is not None else None)
                    cache[0] = (fz, probe)
                p = None
                if probe is not None:
                    try:
                        p = probe(sig)
                    except TypeError:   # unhashable signature value
                        p = None
                hit_frozen = p is not None
                if p is None:
                    p = _resolve(kernel_id, sig)
                fn, launch, complete = self._launch(p, sig)
                count("dispatch.fallback" if not complete else
                      "dispatch.frozen" if hit_frozen else "dispatch.live")
                with obs.scope(kernel_id):
                    return fn(*args, **launch, **kw)

            op.__name__ = self.kernel_id
            op.__qualname__ = self.kernel_id
            op.__doc__ = (f"Tuning-database-dispatched entry point for "
                          f"{self.kernel_id!r} (see repro.kernels.api)."
                          + (f"\n\n{self.fn.__doc__}"
                             if getattr(self.fn, "__doc__", None) else ""))
            op.spec = self
            self._op = op
        return self._op

    def tunable(self, *, seed: int = 0,
                space: Optional[SearchSpace] = None,
                name: Optional[str] = None, **signature) -> TunableKernel:
        """Package this kernel as a `TunableKernel` for `KernelTuner`.

        ``space`` narrows the search space (defaults to the full
        dispatch space); static, hybrid, and empirical modes all work
        when ``make_inputs`` was declared.
        """
        sig = self.normalize(signature)
        sp = space if space is not None else self.search_space(**sig)
        if isinstance(sp, Mapping):
            sp = SearchSpace(dict(sp))
        fwd = {k: v for k, v in sig.items() if k in self._fn_keywords()}

        if self._variants is None:
            def build(p: Params) -> Callable[..., Any]:
                return functools.partial(
                    self.fn, **fwd, **{k: p[k] for k in sp.names})
        else:
            def build(p: Params) -> Callable[..., Any]:
                var = self._variants[p.get(VARIANT_AXIS, self._primary_id)]
                return functools.partial(
                    var.fn, **fwd,
                    **{k: p[k] for k in var.space if k in p})

        if self.make_inputs is None:
            def make_inputs():
                raise NotImplementedError(
                    f"@tuned_kernel({self.kernel_id!r}) declared no "
                    f"make_inputs=; empirical/hybrid tuning needs one")
        else:
            def make_inputs():
                import jax
                return self.make_inputs(jax.random.PRNGKey(seed), **sig)

        if name is None:
            dims = "x".join(str(v) for v in sig.values()
                            if isinstance(v, (int, np.integer)))
            name = f"{self.kernel_id}_{dims}" if dims else self.kernel_id
        return TunableKernel(
            name=name, space=sp, build=build,
            static_info=lambda p: self.static_info(p, **sig),
            make_inputs=make_inputs, reference=self.reference,
            static_info_batch=lambda c: self.static_info_batch(c, **sig))


# ---------------------------------------------------------------------------
# The decorator + the spec registry
# ---------------------------------------------------------------------------

_SPECS: Dict[str, KernelSpec] = {}


def tuned_kernel(kernel_id: str, *,
                 space: Union[Mapping[str, Any], str],
                 signature: Callable[..., Dict[str, Any]],
                 static_info: Callable[..., Dict[str, Any]],
                 fallback: Optional[Callable[..., Dict[str, Any]]] = None,
                 make_inputs: Optional[Callable[..., tuple]] = None,
                 reference: Optional[Callable[..., Any]] = None,
                 pretune: Sequence[Mapping[str, Any]] = (),
                 cuda: Optional[CudaProfile] = None,
                 model: Optional[str] = None,
                 schedule: Optional[Callable[..., Any]] = None,
                 constraints: Any = None,
                 chunk_size: Optional[int] = None,
                 variants: Sequence[KernelVariant] = (),
                 primary_variant: Optional[str] = None):
    """Declare a Pallas kernel as a first-class tuning citizen.

    Decorating ``<name>_pallas`` registers a :class:`KernelSpec` under
    ``kernel_id`` and derives the dispatch wrapper, registry factory,
    tunable-kernel packaging, and fallback params — see the module
    docstring.  The decorated function is returned unchanged (with a
    ``.spec`` attribute when the object allows it), so explicit-block
    callers and tests keep working.
    """
    def deco(fn: Callable[..., Any]) -> Callable[..., Any]:
        spec = KernelSpec(kernel_id=kernel_id, fn=fn, space=space,
                          extract_signature=signature, analysis=static_info,
                          fallback=fallback, make_inputs=make_inputs,
                          reference=reference, pretune=tuple(pretune),
                          cuda=cuda, model=model, schedule=schedule,
                          constraints=constraints,
                          chunk_size=chunk_size, variants=tuple(variants),
                          primary_variant=primary_variant)
        register_spec(spec)
        try:
            fn.spec = spec
        except AttributeError:      # exotic callables may refuse attrs
            pass
        return fn
    return deco


def register_spec(spec: KernelSpec) -> KernelSpec:
    """Register a `KernelSpec` with the dispatch registry (duplicate
    kernel_ids raise — two declarations must not silently shadow)."""
    tuning_cache.registry.register_entry(spec.kernel_id, spec)
    _SPECS[spec.kernel_id] = spec
    return spec


def get_spec(kernel_id: str, default: Any = dataclasses.MISSING
             ) -> KernelSpec:
    spec = _SPECS.get(kernel_id)
    if spec is None:
        if default is not dataclasses.MISSING:
            return default
        raise KeyError(f"no @tuned_kernel declaration for {kernel_id!r}; "
                       f"declared: {registered_kernels()}")
    return spec


def registered_kernels() -> Tuple[str, ...]:
    """kernel_ids declared via `@tuned_kernel`, sorted."""
    return tuple(sorted(_SPECS))


def register_variant(kernel_id: str, variant: KernelVariant) -> None:
    """Register another Pallas implementation of a declared logical op.

    The variant id joins the op's joint search space immediately: the
    kernel's frozen tables thaw and its live memo entries drop (records
    ranked without this variant answer for a stale variant set), and
    the next cold rank scores the new implementation's sub-space
    alongside every existing one.
    """
    get_spec(kernel_id).add_variant(variant)


def unregister_variant(kernel_id: str, variant_id: str) -> KernelVariant:
    """Remove a registered implementation (the primary cannot be
    removed); invalidates the kernel's dispatch state like
    `register_variant`.  Returns the removed variant so callers can
    restore it."""
    return get_spec(kernel_id).remove_variant(variant_id)


def unregister(kernel_id: str) -> None:
    """Remove a declaration (tests / benchmarks cleaning up after
    themselves, or deliberately replacing one); missing ids are a
    no-op.  Also evicts the op wrapper `ops.__getattr__` may have
    memoized into the module, so a re-declaration under the same id
    dispatches through the new spec rather than a stale global."""
    import sys
    _SPECS.pop(kernel_id, None)
    tuning_cache.registry.unregister(kernel_id)
    ops_mod = sys.modules.get("repro.kernels.ops")
    if ops_mod is not None:
        ops_mod.__dict__.pop(kernel_id, None)
