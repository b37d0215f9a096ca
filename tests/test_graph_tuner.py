"""GraphTuner: the paper's static search applied to graph-level knobs
(microbatch depth) — compile-only, zero execution, on an 8-device
sub-mesh (subprocess to own the device count)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.configs import get_smoke
    from repro.core import GraphTuner, SearchSpace
    from repro.distributed import TrainStepConfig, make_train_step
    from repro.launch.specs import cell_inputs
    from repro.models import build_model
    from repro.models.config import ShapeSpec
    from repro.optim import AdamWConfig

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_smoke("starcoder2-3b")
    model = build_model(cfg)
    shape = ShapeSpec("t", 64, 8, "train")
    args = cell_inputs(model, shape, mesh)

    def lower_fn(params):
        step = make_train_step(
            model, AdamWConfig(), mesh=mesh,
            step_cfg=TrainStepConfig(microbatches=params["mb"]))
        with mesh:
            return jax.jit(step).lower(*args)

    tuner = GraphTuner(SearchSpace({"mb": (1, 2)}), lower_fn,
                       chips=8, model_flops=model.model_flops(shape))
    best, terms, hist = tuner.tune()
    print(json.dumps({
        "best_mb": best["mb"],
        "n_scored": len(hist),
        "all_finite": all(t < float("inf") for _, t in hist),
        "dominant": terms.dominant,
    }))
""")


@pytest.mark.slow
def test_graph_tuner_scores_all_candidates_without_execution():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_scored"] == 2
    assert rec["all_finite"]
    assert rec["best_mb"] in (1, 2)
    assert rec["dominant"] in ("compute", "memory", "collective")
