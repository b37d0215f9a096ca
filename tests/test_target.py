"""Hardware-target registry tests (ISSUE 3 acceptance).

Covers: name resolution, the process-default stack (explicit >
REPRO_TUNING_TARGET > autodetect > v5e), `use_target` scoping incl.
exception safety, per-target isolation of cache keys / dispatch-memo
entries / winning params, lazy warming of the shipped per-target
databases, and the end-to-end acceptance criterion — an unmodified
program dispatches with the chip picked by the environment variable,
served entirely from the shipped database.
"""
import json
import os
import subprocess
import sys

import pytest

from repro import tuning_cache
from repro.core import (TPU_TABLE, TPU_V4, TPU_V5E, TPU_V5P, TPU_V6E,
                        TpuSpec, default_target, resolve_target,
                        set_default_target, use_target)
from repro.core import target as target_mod
from repro.tuning_cache import TuningDatabase, fingerprint_spec
from repro.tuning_cache import registry as registry_mod
from repro.tuning_cache.cli import SHIPPED_TARGETS
from repro.tuning_cache.cli import main as cli_main

import repro.kernels  # noqa: F401  (registers dispatch problems)


@pytest.fixture(autouse=True)
def _fresh_target_and_db(monkeypatch):
    """Isolate each test from ambient target/env/database state."""
    monkeypatch.delenv(target_mod.ENV_TARGET, raising=False)
    set_default_target(None)
    tuning_cache.set_default_db(TuningDatabase())
    yield
    set_default_target(None)
    tuning_cache.reset_default_db()


# ---------------------------------------------------------------------------
# Resolution + the TPU table
# ---------------------------------------------------------------------------


def test_resolve_target_aliases():
    assert resolve_target("tpu-v5p") is TPU_V5P
    assert resolve_target("v5p") is TPU_V5P
    assert resolve_target("TPU_V4") is TPU_V4
    assert resolve_target("TPU v6e") is TPU_V6E
    # jax device_kind spellings
    assert resolve_target("TPU v5 lite") is TPU_V5E
    assert resolve_target("TPU v6 lite") is TPU_V6E
    assert resolve_target("TPU v5") is TPU_V5P    # v5p's device_kind
    assert resolve_target("TPU v4") is TPU_V4
    # spec passthrough
    custom = TpuSpec(hbm_bw=1.0)
    assert resolve_target(custom) is custom
    with pytest.raises(KeyError):
        resolve_target("tpu-v99")


def test_tpu_table_is_per_chip_distinct():
    canonical = {k: v for k, v in TPU_TABLE.items() if k.startswith("tpu-")}
    assert set(canonical) == {"tpu-v4", "tpu-v5e", "tpu-v5p", "tpu-v6e"}
    fps = {fingerprint_spec(s) for s in canonical.values()}
    assert len(fps) == 4                      # no two chips collide
    # ICI topology drives links-per-chip: 3D torus chips have 6.
    assert TPU_V4.ici_links == 6 and TPU_V5P.ici_links == 6
    assert TPU_V5E.ici_links == 4 and TPU_V6E.ici_links == 4


# ---------------------------------------------------------------------------
# Default-target stack
# ---------------------------------------------------------------------------


def test_default_target_fallback_is_v5e():
    # CPU test box: no TPU to detect, no env, no explicit pin.
    assert default_target() is TPU_V5E


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", TPU_V5E),
    ("tpu", "TPU v5", TPU_V5P),
    ("tpu", "TPU v99 imaginary", KeyError),
    ("cpu", "cpu", TPU_V5E),
])
def test_detected_target_never_guesses_a_tpu(monkeypatch, platform, kind,
                                             want):
    """A TPU backend is analyzed as the chip it reports, or not at all;
    only a non-TPU backend gets the v5e analysis target."""
    import jax

    class FakeDevice:
        pass
    dev = FakeDevice()
    dev.platform, dev.device_kind = platform, kind
    with monkeypatch.context() as m:
        m.setattr(jax, "devices", lambda *a, **k: [dev])
        m.setattr(target_mod, "_detected", None)
        if want is KeyError:
            with pytest.raises(KeyError, match="unknown hardware target"):
                default_target()
        else:
            assert default_target() is want


def test_env_override(monkeypatch):
    monkeypatch.setenv(target_mod.ENV_TARGET, "tpu-v5p")
    assert default_target() is TPU_V5P
    # explicit set shadows the environment ...
    set_default_target("tpu-v6e")
    assert default_target() is TPU_V6E
    # ... and clearing it falls back to the env again
    set_default_target(None)
    assert default_target() is TPU_V5P


def test_use_target_restores_prior_default():
    set_default_target("tpu-v4")
    with use_target("tpu-v5p") as spec:
        assert spec is TPU_V5P
        assert default_target() is TPU_V5P
        with use_target(TPU_V6E):             # nests
            assert default_target() is TPU_V6E
        assert default_target() is TPU_V5P
    assert default_target() is TPU_V4


def test_use_target_restores_on_exception():
    with pytest.raises(RuntimeError):
        with use_target("tpu-v5p"):
            raise RuntimeError("boom")
    assert default_target() is TPU_V5E


def test_use_target_is_thread_local():
    """`use_target` scopes are context-local: one thread pinning v5p
    around an analysis can never leak v5p into another thread."""
    import threading
    seen, ready, release = {}, threading.Barrier(2), threading.Barrier(2)

    def worker(name, target):
        with use_target(target):
            ready.wait(timeout=10)       # both scopes active at once
            seen[name] = default_target()
            release.wait(timeout=10)

    threads = [threading.Thread(target=worker, args=("a", "tpu-v5p")),
               threading.Thread(target=worker, args=("b", "tpu-v6e"))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20)
    assert seen == {"a": TPU_V5P, "b": TPU_V6E}
    assert default_target() is TPU_V5E   # main thread never saw either


# ---------------------------------------------------------------------------
# Per-target isolation of the tuning stack
# ---------------------------------------------------------------------------

_SIG = dict(m=512, n=512, k=512, dtype="float32")


def test_two_targets_two_cache_keys():
    """Same kernel/signature under two targets -> two database records
    with distinct spec fingerprints."""
    db = TuningDatabase()
    tuning_cache.lookup_or_tune("matmul", db=db, spec=TPU_V5E, **_SIG)
    tuning_cache.lookup_or_tune("matmul", db=db, spec=TPU_V5P, **_SIG)
    recs = list(db.records())
    assert len(recs) == 2
    assert len({r.key.spec_fingerprint for r in recs}) == 2
    assert db.stats.tunes == 2                # no cross-target hit


def test_two_targets_two_dispatch_memo_entries():
    """The warm-dispatch memo keys on the spec fingerprint: switching
    targets can never serve the other chip's memoized params."""
    tuning_cache.clear_dispatch_memo()
    with use_target("tpu-v5e"):
        tuning_cache.lookup_or_tune("matmul", **_SIG)
    with use_target("tpu-v5p"):
        tuning_cache.lookup_or_tune("matmul", **_SIG)
    fps = {k[2] for k in registry_mod.dispatch_memo_keys()}
    assert fingerprint_spec(TPU_V5E) in fps
    assert fingerprint_spec(TPU_V5P) in fps


def test_winning_params_differ_where_budgets_differ():
    """atax 2048x2048 f32: bm=1024 tiles fit v5p's VMEM budget but not
    v5e's, so the statically-ranked winner is chip-specific (the
    paper's Table-I observation transplanted to TPU)."""
    sig = dict(m=2048, n=2048, dtype="float32")
    db = TuningDatabase()
    p_v5e = tuning_cache.lookup_or_tune("atax", db=db, spec=TPU_V5E, **sig)
    p_v5p = tuning_cache.lookup_or_tune("atax", db=db, spec=TPU_V5P, **sig)
    assert p_v5e != p_v5p


def test_kernel_tuner_pinned_to_its_spec():
    """A KernelTuner built for one chip keeps analyzing for that chip
    even when the ambient default changes mid-life."""
    from repro.kernels import make_tunable_matmul
    from repro.core import KernelTuner
    tuner = KernelTuner(make_tunable_matmul(512, 512, 512), spec=TPU_V5P,
                        db=None)
    with use_target("tpu-v5e"):
        info = tuner._info(tuner._mid_params())
    # v5p occupancy: budget is 32 MiB, so the mid-config ratio must be
    # computed against v5p's budget, not ambient v5e's 16 MiB.
    assert info.occupancy.vmem_ratio == pytest.approx(
        info.occupancy.vmem_bytes / TPU_V5P.vmem_bytes)


# ---------------------------------------------------------------------------
# Shipped per-target databases
# ---------------------------------------------------------------------------


def test_warm_pretuned_is_lazy_and_per_target():
    db = tuning_cache.get_default_db()
    sig = dict(m=1024, n=1024, k=1024, dtype="float32")
    with use_target("tpu-v5e"):
        tuning_cache.lookup_or_tune("matmul", **sig)
    assert "tpu-v5e" in db.warmed_targets
    assert "tpu-v5p" not in db.warmed_targets   # other chips stay cold
    n0 = len(db)
    with use_target("tpu-v5p"):
        tuning_cache.lookup_or_tune("matmul", **sig)
    assert "tpu-v5p" in db.warmed_targets
    assert len(db) > n0                      # v5p records folded in
    assert db.stats.tunes == 0               # served from the shipped dbs


def test_pretune_verify_all_targets(tmp_path):
    """Every shipped pretuned JSONL must be regenerable bit-for-bit."""
    assert cli_main(["--db", str(tmp_path / "db"), "pretune",
                     "--verify", "--all-targets"]) == 0


def test_pretune_verify_detects_tampering(tmp_path):
    shipped = tuning_cache.pretuned_path("tpu-v5e")
    tampered = tmp_path / "tpu_v5e.jsonl"
    lines = open(shipped).read().splitlines()
    rec = json.loads(lines[0])
    rec["params"] = {k: 8 for k in rec["params"]}
    tampered.write_text("\n".join([json.dumps(rec, sort_keys=True)]
                                  + lines[1:]) + "\n")
    with pytest.raises(SystemExit):
        cli_main(["--db", str(tmp_path / "db"), "pretune", "--verify",
                  "--target", "tpu-v5e", "--out", str(tampered)])


# ---------------------------------------------------------------------------
# Acceptance criterion: env-selected target, shipped-db hit, zero tunes
# ---------------------------------------------------------------------------

_ACCEPTANCE_PROG = r"""
import json, sys
import repro.kernels
from repro import tuning_cache
from repro.core import default_target
from repro.core.predict import default_tpu_model
from repro.tuning_cache import fingerprint_spec, make_key
from repro.tuning_cache.registry import normalize_signature

sig = dict(m=1024, n=1024, k=1024, dtype="float32")
params = tuning_cache.lookup_or_tune("matmul", **sig)
db = tuning_cache.get_default_db()
spec = default_target()
key = make_key("matmul", spec=spec,
               model_name=default_tpu_model(spec, mode="max").fingerprint(),
               **normalize_signature("matmul", sig))
print(json.dumps({"target": spec.name, "params": params,
                  "digest": key.digest, "tunes": db.stats.tunes}))
"""


def _run_acceptance(target_name):
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    env["REPRO_TUNING_TARGET"] = target_name
    env.pop("REPRO_TUNING_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", _ACCEPTANCE_PROG],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_acceptance_env_target_dispatches_from_shipped_db():
    """`REPRO_TUNING_TARGET=tpu-v5p python ...` dispatches matmul with
    v5p-ranked params straight from the shipped v5p database (zero
    model evaluations), the same program under tpu-v5e returns the v5e
    ranking, and the two runs resolve different cache keys."""
    a = _run_acceptance("tpu-v5p")
    b = _run_acceptance("tpu-v5e")
    assert a["target"] == "tpu-v5p" and b["target"] == "tpu-v5e"
    assert a["tunes"] == 0 and b["tunes"] == 0   # pure shipped-db hits
    assert a["digest"] != b["digest"]            # distinct cache keys
    for name, run in (("tpu_v5p", a), ("tpu_v5e", b)):
        path = os.path.join(tuning_cache.pretuned_dir(), f"{name}.jsonl")
        shipped = {json.loads(l)["key"]["signature"]: json.loads(l)["params"]
                   for l in open(path)}
        match = [p for s, p in shipped.items()
                 if '"k":1024,"m":1024' in s and '"n":1024' in s
                 and "float32" in s]
        assert run["params"] in match
