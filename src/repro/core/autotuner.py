"""Autotuner (the Orio-integration layer, paper §III-C / §IV-C).

Two tuners:

* :class:`KernelTuner` — tunes a Pallas kernel's launch configuration
  (block shapes, unroll, dimension semantics...).  Modes:

  - ``static``     zero executions: rank by the predictive model +
                   occupancy feasibility, return the model argmin
                   (the paper's headline capability),
  - ``hybrid``     static shortlist, then empirically time the top-k
                   (the paper's "first stage of regular autotuning"),
  - ``empirical``  classic Orio: a search strategy over measured times.

* :class:`GraphTuner` — the beyond-paper extension: tunes *graph-level*
  knobs (sharding layout, remat policy, microbatch size) by AOT
  lower+compile and ranking with the 3-term roofline — still zero
  executions, which is exactly the paper's thesis applied at
  datacenter scale.

Empirical timing protocol: the paper ran each variant 10 times and kept
the 5th sorted trial; we use the median of ``repeats`` wall-clock runs
(same robustness intent; noted in DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
import logging
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.hw import TpuSpec, require_tpu, resolve_target
from repro.core.mix import InstructionMix, intensity, classify_boundedness
from repro.core.target import use_target
from repro.core.occupancy import TpuOccupancy
from repro.core.predict import (CostModel, default_tpu_model, spearman,
                                static_times_batch)
from repro.core.search import (ExhaustiveSearch, Params, SearchResult,
                               SearchSpace, StaticPrunedSearch, _Base)

__all__ = [
    "KernelStaticInfo", "TunableKernel", "TuningReport",
    "KernelTuner", "GraphTuner", "make_intensity_rule",
]

_log = logging.getLogger(__name__)


@dataclasses.dataclass
class KernelStaticInfo:
    """Everything the static analyzer derives for one configuration."""

    mix: InstructionMix
    occupancy: Optional[TpuOccupancy] = None
    # every block obeys the TPU block rule (`kernels.common.tile_legal`)
    tiles_legal: bool = True

    def feasible(self) -> bool:
        return self.tiles_legal and (self.occupancy is None
                                     or self.occupancy.fits_vmem)

    def static_time(self, model: CostModel) -> float:
        """Predicted seconds; infeasible configs get +inf."""
        if not self.feasible():
            return math.inf
        t_model = model.time(self.mix)
        if self.occupancy is not None:
            t_pipe = (self.occupancy.predicted_step_time
                      * max(self.occupancy.grid_steps, 1))
            return max(t_model, t_pipe)
        return t_model


@dataclasses.dataclass
class TunableKernel:
    """A kernel + its tuning space (what an Orio annotation declares).

    ``static_info_batch``, when provided, is the struct-of-arrays
    analyzer: it takes a dict of (N,) value columns (one per space
    axis; see `SearchSpace.enumerate_lattice`) and returns a
    `repro.kernels.common.BatchStaticInfo` whose rows match
    ``static_info`` exactly.  The tuner ranks through it when present;
    the scalar builder remains the parity fallback and the per-point
    probe.
    """

    name: str
    space: SearchSpace
    build: Callable[[Params], Callable[..., Any]]
    static_info: Callable[[Params], KernelStaticInfo]
    make_inputs: Callable[[], tuple]
    reference: Optional[Callable[..., Any]] = None
    static_info_batch: Optional[Callable[[Dict[str, np.ndarray]], Any]] = None


@dataclasses.dataclass
class TuningReport:
    kernel: str
    mode: str
    best_params: Params
    best_predicted_s: float
    best_measured_s: Optional[float]
    space_size: int
    static_rank_time_s: float          # cost of the static pass itself
    empirical_evals: int
    search_space_reduction: float      # Fig. 6 metric
    spearman_static_vs_measured: Optional[float]
    boundedness: str
    intensity: float
    table: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    from_cache: bool = False           # served from the tuning database

    def summary(self) -> str:
        sp = ("%.3f" % self.spearman_static_vs_measured
              if self.spearman_static_vs_measured is not None else "n/a")
        return (f"[{self.kernel}:{self.mode}] best={self.best_params} "
                f"pred={self.best_predicted_s:.3e}s "
                f"evals={self.empirical_evals}/{self.space_size} "
                f"reduction={100*self.search_space_reduction:.1f}% "
                f"spearman={sp} {self.boundedness} I={self.intensity:.2f}")


def make_intensity_rule(mix: InstructionMix,
                        space: SearchSpace,
                        size_axes: Sequence[str],
                        threshold: float = 4.0) -> Callable[[Params], bool]:
    """The paper's rule-based heuristic (§III-C).

    intensity > threshold (compute-bound)  ⇒ keep the *upper* half of
    each size axis (bigger tiles feed the MXU);
    intensity ≤ threshold (memory-bound)   ⇒ keep the *lower* half
    (smaller tiles pipeline DMA better).
    """
    hot = intensity(mix) > threshold

    def rule(p: Params) -> bool:
        for ax in size_axes:
            vals = space.axes.get(ax)
            if not vals:
                continue
            order = sorted(vals)
            half = order[len(order) // 2:] if hot else order[:max(1, len(order) // 2)]
            if p[ax] not in half:
                return False
        return True

    return rule


def _median_time(fn: Callable[..., Any], inputs: tuple, repeats: int) -> float:
    import jax
    # warmup/compile
    out = fn(*inputs)
    jax.block_until_ready(out)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*inputs)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


class KernelTuner:
    """Tunes one Pallas kernel; results persist in the tuning database.

    ``db`` controls result reuse: the default sentinel ``"default"``
    resolves to :func:`repro.tuning_cache.get_default_db` (the
    process-wide LRU + optional on-disk store), ``None`` disables
    caching, and any :class:`~repro.tuning_cache.TuningDatabase` is used
    as-is.  On a cache hit :meth:`tune` returns without a single
    cost-model evaluation.
    """

    def __init__(self, kernel: TunableKernel,
                 model: Optional[CostModel] = None,
                 spec: Optional[TpuSpec] = None,
                 repeats: int = 5,
                 keep_frac: float = 0.125,
                 use_rule: bool = True,
                 size_axes: Optional[Sequence[str]] = None,
                 seed: int = 0,
                 db: Any = "default"):
        self.kernel = kernel
        # KernelTuner drives the Pallas pipeline model; a GpuSpec target
        # must fail here with the family-check error, not deeper in
        # default_tpu_model (GPU rankings go through lookup_or_tune)
        self.spec = require_tpu(spec, type(self).__name__)
        self.model = model or default_tpu_model(self.spec, mode="max")
        self.repeats = repeats
        self.keep_frac = keep_frac
        self.use_rule = use_rule
        self.size_axes = list(size_axes) if size_axes else [
            a for a in kernel.space.names
            if a.startswith("b") or "block" in a or "tile" in a]
        self.seed = seed
        self.db = db
        self._info_cache: Dict[Tuple, KernelStaticInfo] = {}

    # -- static machinery ----------------------------------------------------
    # Kernel-supplied static_info builders resolve their own spec from
    # the default target, so every analysis call runs under
    # `use_target(self.spec)`: a tuner constructed for one chip keeps
    # analyzing for that chip whatever the ambient default is.
    def _info(self, p: Params) -> KernelStaticInfo:
        key = tuple(str(p[k]) for k in self.kernel.space.names)
        if key not in self._info_cache:
            with use_target(self.spec):
                self._info_cache[key] = self.kernel.static_info(p)
        return self._info_cache[key]

    def static_cost(self, p: Params) -> float:
        return self._info(p).static_time(self.model)

    def static_cost_batch(self, pts: Sequence[Params]) -> np.ndarray:
        """Score a candidate set in one vectorized model pass.

        When the kernel registers a struct-of-arrays builder the whole
        pass is array math: the candidate dicts are transposed into
        value columns, analyzed in one `static_info_batch` call, and
        scored directly from the feature matrix — no KernelStaticInfo
        objects at all.  Kernels without a batch builder fall back to
        the scalar analyzer per point.
        """
        if self.kernel.static_info_batch is not None:
            cols = {k: np.asarray([p[k] for p in pts])
                    for k in self.kernel.space.names}
            with use_target(self.spec):
                b = self.kernel.static_info_batch(cols)
            return static_times_batch(None, self.model, F=b.F, pipe=b.pipe,
                                      feasible=b.feasible)
        return static_times_batch([self._info(p) for p in pts], self.model)

    def static_cost_cols(self, cols: Dict[str, np.ndarray]) -> np.ndarray:
        """Columns-based scorer for the streaming shortlist: score one
        `SearchSpace.iter_lattice` chunk without building params dicts.
        Only available when the kernel has a struct-of-arrays builder."""
        if self.kernel.static_info_batch is None:
            raise TypeError(
                f"kernel {self.kernel.name!r} has no static_info_batch; "
                "the streaming shortlist needs a columns analyzer")
        with use_target(self.spec):
            b = self.kernel.static_info_batch(cols)
        return static_times_batch(None, self.model, F=b.F, pipe=b.pipe,
                                  feasible=b.feasible)

    def _mid_params(self) -> Params:
        return {k: v[len(v) // 2]
                for k, v in self.kernel.space.axes.items()}

    def representative_mix(self) -> InstructionMix:
        return self._info(self._mid_params()).mix

    # -- tuning-database plumbing ---------------------------------------------
    def _database(self):
        if self.db == "default":
            from repro.tuning_cache import get_default_db
            return get_default_db()
        return self.db

    def _analysis_fingerprint(self) -> str:
        """Static-analysis identity of the kernel instance.

        Kernel names encode shapes only, so two TunableKernels with the
        same shapes but different dtype (or e.g. flash causal=False)
        would otherwise share a key.  The mid-config instruction mix +
        occupancy step time reflect every analytic input, so they
        disambiguate without the factory having to name them all.
        """
        info = self._info(self._mid_params())
        # normalize through float(): analytic builders may hand back
        # numpy scalars, whose repr differs across numpy majors
        parts = [repr(float(getattr(info.mix, f))) for f in (
            "mxu_flops", "vpu_flops", "trans_flops", "hbm_bytes",
            "vmem_bytes", "ctrl_ops", "reg_ops")]
        if info.occupancy is not None:
            parts.append(repr(float(info.occupancy.predicted_step_time)))
            parts.append(repr(int(info.occupancy.grid_steps)))
        import hashlib
        return hashlib.sha256("|".join(parts).encode()).hexdigest()[:12]

    def _cache_key(self, mode: str, empirical_budget: Optional[int],
                   strategy: Optional[_Base]):
        from repro.tuning_cache import make_key
        return make_key(
            f"tuner/{self.kernel.name}", spec=self.spec, mode=mode,
            model_name=self.model.fingerprint(),
            analysis=self._analysis_fingerprint(),
            axes={k: list(map(str, v))
                  for k, v in self.kernel.space.axes.items()},
            keep_frac=self.keep_frac, use_rule=self.use_rule,
            size_axes=list(self.size_axes), repeats=self.repeats,
            empirical_budget=empirical_budget,
            # full strategy config, not just the class: two differently
            # configured SimulatedAnnealing instances must not collide.
            # Only primitive attrs participate — object reprs embed
            # memory addresses and would make every key unique.
            strategy=(type(strategy).__name__
                      + repr(sorted(
                          (k, v) for k, v in vars(strategy).items()
                          if isinstance(v, (int, float, str, bool,
                                            type(None)))))
                      if strategy else None))

    def _report_from_record(self, rec, mode: str) -> "TuningReport":
        ex = rec.extras
        return TuningReport(
            kernel=self.kernel.name, mode=mode,
            best_params=dict(rec.params),
            best_predicted_s=rec.predicted_s,
            best_measured_s=rec.measured_s,
            space_size=rec.space_size,
            static_rank_time_s=0.0,
            empirical_evals=0,
            search_space_reduction=ex.get("search_space_reduction", 1.0),
            spearman_static_vs_measured=ex.get("spearman"),
            boundedness=ex.get("boundedness", "unknown"),
            intensity=ex.get("intensity", 0.0),
            from_cache=True)

    # -- tuning modes ----------------------------------------------------------
    def tune(self, mode: str = "static",
             strategy: Optional[_Base] = None,
             empirical_budget: Optional[int] = None) -> TuningReport:
        db = self._database()
        key = self._cache_key(mode, empirical_budget, strategy) \
            if db is not None else None
        if db is not None:
            rec = db.lookup(key)
            if rec is not None:
                # Cache hit: one mid-config static_info (key fingerprint),
                # zero cost-model evaluations, no space ranking.
                return self._report_from_record(rec, mode)
        space = self.kernel.space
        mix0 = self.representative_mix()
        rule = (make_intensity_rule(mix0, space, self.size_axes)
                if self.use_rule else None)
        t0 = time.perf_counter()

        def objective(p: Params) -> float:
            fn = self.kernel.build(p)
            return _median_time(fn, self.kernel.make_inputs(), self.repeats)

        table: List[Dict[str, Any]] = []
        measured_for_corr: List[float] = []
        predicted_for_corr: List[float] = []

        cols_scorer = (self.static_cost_cols
                       if self.kernel.static_info_batch is not None else None)
        if mode == "static":
            pruner = StaticPrunedSearch(self.static_cost,
                                        keep_frac=self.keep_frac,
                                        rule=rule, seed=self.seed,
                                        static_cost_batch=self.static_cost_batch,
                                        static_cost_cols=cols_scorer)
            res = pruner.minimize(objective, space, empirical_budget=0)
            static_time = time.perf_counter() - t0
            best_pred = res.best_value
            best_meas = None
        elif mode == "hybrid":
            pruner = StaticPrunedSearch(self.static_cost,
                                        keep_frac=self.keep_frac,
                                        rule=rule, seed=self.seed,
                                        static_cost_batch=self.static_cost_batch,
                                        static_cost_cols=cols_scorer)
            short = pruner.shortlist(space)
            static_time = time.perf_counter() - t0
            cap = empirical_budget or len(short)
            hist = []
            for p, pred in short[:cap]:
                meas = objective(p)
                hist.append((p, meas))
                predicted_for_corr.append(pred)
                measured_for_corr.append(meas)
                table.append({"params": p, "predicted_s": pred,
                              "measured_s": meas})
            best_p, best_meas = min(hist, key=lambda t: t[1])
            best_pred = self.static_cost(best_p)
            res = SearchResult(best_p, best_meas, len(hist), space.size,
                               len(short), hist)
        elif mode == "empirical":
            strat = strategy or ExhaustiveSearch(seed=self.seed)
            res = strat.minimize(objective, space, budget=empirical_budget)
            static_time = 0.0
            best_pred = self.static_cost(res.best_params)
            best_meas = res.best_value
            for p, v in res.history:
                predicted_for_corr.append(self.static_cost(p))
                measured_for_corr.append(v)
                table.append({"params": p,
                              "predicted_s": predicted_for_corr[-1],
                              "measured_s": v})
        else:
            raise ValueError(f"unknown mode {mode!r}")

        corr = (spearman(predicted_for_corr, measured_for_corr)
                if len(measured_for_corr) >= 3 else None)
        info = self._info(res.best_params)
        report = TuningReport(
            kernel=self.kernel.name, mode=mode,
            best_params=res.best_params,
            best_predicted_s=float(best_pred),
            best_measured_s=best_meas,
            space_size=space.size,
            static_rank_time_s=static_time,
            empirical_evals=res.evaluations,
            search_space_reduction=res.search_space_reduction,
            spearman_static_vs_measured=corr,
            boundedness=classify_boundedness(info.mix),
            intensity=intensity(info.mix),
            table=table,
        )
        if db is not None:
            from repro.tuning_cache import TuningRecord
            from repro.tuning_cache.store import now_unix
            db.put(TuningRecord(
                key=key, params=dict(report.best_params),
                predicted_s=report.best_predicted_s,
                measured_s=report.best_measured_s,
                space_size=report.space_size, source=mode,
                created_unix=now_unix(),
                extras={
                    "search_space_reduction": report.search_space_reduction,
                    "spearman": report.spearman_static_vs_measured,
                    "boundedness": report.boundedness,
                    "intensity": report.intensity,
                }))
        return report


class GraphTuner:
    """Static (compile-only) tuner for graph-level knobs.

    ``lower_fn(params)`` must return a ``jax.stages.Lowered``; we compile
    it AOT and score with the 3-term roofline.  No device execution —
    the direct datacenter-scale application of the paper's thesis.

    ``db`` + ``cache_signature`` opt into the tuning database: because
    ``lower_fn`` is an opaque callable, the caller must supply the
    signature kwargs (arch name, batch, seq, ...) that make the result
    reusable.  A cached hit skips every AOT lower+compile and returns
    ``(params, terms, [])`` with terms rebuilt as a
    :class:`~repro.core.roofline.RooflineTerms` (or ``None`` if the
    stored record cannot be rebuilt); history is not cached.
    """

    def __init__(self, space: SearchSpace,
                 lower_fn: Callable[[Params], Any],
                 chips: int, model_flops: float,
                 spec: Optional[TpuSpec] = None,
                 ici_links: Optional[int] = None,
                 db: Any = None,
                 cache_signature: Optional[Dict[str, Any]] = None):
        self.space = space
        self.lower_fn = lower_fn
        self.chips = chips
        self.model_flops = model_flops
        self.spec = require_tpu(spec, type(self).__name__)
        self.ici_links = (self.spec.ici_links if ici_links is None
                          else ici_links)
        self.db = db
        self.cache_signature = cache_signature

    def score(self, p: Params) -> Tuple[float, Any]:
        from repro.core.roofline import roofline_from_artifacts
        lowered = self.lower_fn(p)
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        text = compiled.as_text()
        terms = roofline_from_artifacts(
            name=str(p), cost=cost, hlo_text=text, chips=self.chips,
            model_flops=self.model_flops, spec=self.spec,
            ici_links=self.ici_links)
        t = max(terms.t_compute, terms.t_memory, terms.t_collective)
        return t, terms

    def _cache_key(self):
        if self.db is None or self.cache_signature is None:
            return None
        from repro.tuning_cache import make_key
        return make_key(
            "graph", spec=self.spec, mode="graph",
            chips=self.chips, model_flops=self.model_flops,
            ici_links=self.ici_links,
            axes={k: list(map(str, v)) for k, v in self.space.axes.items()},
            **self.cache_signature)

    def tune(self) -> Tuple[Params, Any, List[Tuple[Params, float]]]:
        key = self._cache_key()
        if key is not None:
            rec = self.db.lookup(key)
            if rec is not None:
                terms = rec.extras.get("terms")
                if isinstance(terms, dict):
                    # rebuild the dataclass so hit and miss return the
                    # same type (callers access .t_compute etc.)
                    from repro.core.roofline import RooflineTerms
                    try:
                        terms = RooflineTerms(**terms)
                    except TypeError:
                        terms = None
                return dict(rec.params), terms, []
        hist: List[Tuple[Params, float]] = []
        best_p, best_t, best_terms = None, math.inf, None
        for p in self.space.enumerate():
            try:
                t, terms = self.score(p)
            except (ValueError, TypeError, LookupError, RuntimeError,
                    ArithmeticError, AssertionError) as e:
                # Infeasible candidate (unshardable layout, compile
                # rejection — XlaRuntimeError subclasses RuntimeError;
                # LookupError covers candidate-indexed tables in user
                # lower_fns).  Scored +inf, never wins; params logged so
                # a sharding that silently loses every time is
                # diagnosable.
                _log.debug("GraphTuner: candidate %s infeasible: %s",
                           p, e, exc_info=True)
                hist.append((p, math.inf))
                continue
            hist.append((p, t))
            if t < best_t:
                best_p, best_t, best_terms = p, t, terms
        if key is not None and best_p is not None:
            from repro.tuning_cache import TuningRecord
            from repro.tuning_cache.store import now_unix
            terms_d = (dataclasses.asdict(best_terms)
                       if dataclasses.is_dataclass(best_terms) else None)
            self.db.put(TuningRecord(
                key=key, params=dict(best_p), predicted_s=float(best_t),
                space_size=self.space.size, source="graph",
                created_unix=now_unix(), extras={"terms": terms_d}))
        return best_p, best_terms, hist

    @classmethod
    def tune_config(cls, cfg, *, batch: int = 2, prompt_len: int = 64,
                    decode: bool = True, spec=None, db=None,
                    mode: str = "static",
                    tune: bool = True) -> Dict[str, Any]:
        """Graph-level pretune of one serving config (DESIGN.md §15).

        Enumerates every ``(kernel_id, signature)`` instance the
        config's serving path dispatches — a `jax.eval_shape` of
        prefill (and, with ``decode=True``, one decode step) under
        ``use_tuned_layers`` with dispatch collection on, so no kernel
        runs and no params materialize — then resolves each distinct
        instance through `repro.tuning_cache.lookup_or_tune` (the
        streaming SoA rank).  After ``freeze()``, serving that config
        dispatches 100% through the frozen tables with zero runtime
        tunes.

        ``cfg`` is a `ModelConfig` (callers pick real vs smoke via
        `repro.configs.get_config` / `get_smoke`).  Returns a report::

            {"config": name, "batch": B, "prompt_len": S,
             "instances": [{"kernel": id, "signature": {...},
                            "params": {...} | None}, ...],
             "dispatches": total_collected, "tuned": n_resolved}
        """
        import jax
        import jax.numpy as jnp
        from repro import obs
        from repro.distributed import make_serve_fns
        from repro.kernels import api
        from repro.models import build_model
        from repro.models.layers import use_tuned_layers

        with obs.span("pretune", config=cfg.name, batch=batch,
                      prompt_len=prompt_len):
            model = build_model(cfg)
            params_abs = model.abstract_params()
            prefill, decode_step = make_serve_fns(model)
            batch_abs = {"tokens": jax.ShapeDtypeStruct((batch, prompt_len),
                                                        jnp.int32)}
            if cfg.frontend == "frames":
                batch_abs["frames"] = jax.ShapeDtypeStruct(
                    (batch, cfg.enc_seq, cfg.d_model), jnp.dtype(cfg.dtype))
            with obs.span("pretune.enumerate"), use_tuned_layers(), \
                    api.collect_dispatches() as col:
                _, cache_abs = jax.eval_shape(prefill, params_abs, batch_abs)
                if decode:
                    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
                    jax.eval_shape(decode_step, params_abs, cache_abs, tok)
            # dedup preserving first-seen order (layers repeat instances)
            seen: Dict[Any, Dict[str, Any]] = {}
            for kid, sig in col:
                k = (kid, tuple(sorted(sig.items())))
                if k not in seen:
                    seen[k] = {"kernel": kid, "signature": sig,
                               "params": None}
            report = {"config": cfg.name, "batch": batch,
                      "prompt_len": prompt_len,
                      "instances": list(seen.values()),
                      "dispatches": len(col), "tuned": 0}
            if tune:
                from repro.tuning_cache import lookup_or_tune
                with obs.span("pretune.resolve",
                              instances=len(report["instances"])):
                    for inst in report["instances"]:
                        kw = {} if db is None else {"db": db}
                        inst["params"] = lookup_or_tune(
                            inst["kernel"], spec=spec, mode=mode, **kw,
                            **inst["signature"])
                        report["tuned"] += 1
            return report
