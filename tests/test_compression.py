"""int8 + error-feedback gradient compression tests."""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.compression import dequantize_int8, quantize_int8

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_quantizer_roundtrip_bounded_error():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((128, 64)).astype(np.float32))
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x)).max()
    # max error is one quantization step = scale
    assert err <= float(s) + 1e-7


def test_quantizer_handles_zeros_and_extremes():
    q, s = quantize_int8(jnp.zeros((8, 8), jnp.float32))
    assert np.all(np.asarray(q) == 0)
    x = jnp.asarray([[1e20, -1e20]], jnp.float32)
    q, s = quantize_int8(x)
    back = np.asarray(dequantize_int8(q, s))
    np.testing.assert_allclose(back, np.asarray(x), rtol=1e-2)


_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.distributed.compression import ef_compress_grads

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    grads = {"w": jnp.full((16, 8), 3.0, jnp.float32)}
    opt = {"count": jnp.zeros((), jnp.int32)}
    with mesh:
        out, new_opt = jax.jit(
            lambda g, o: ef_compress_grads(g, o, mesh))(grads, opt)
    print(json.dumps({
        "w00": float(out["w"][0, 0]),
        "has_ef": "ef" in new_opt,
    }))
""")


@pytest.mark.slow
def test_ef_compression_mean_preserving_on_submesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(rec["w00"] - 3.0) < 0.1   # psum/n preserves the value
    assert rec["has_ef"]
