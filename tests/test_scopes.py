"""Named scopes put the model's layer path into op metadata and change
nothing else: the optimized program is the same with the scopes live or
turned off, and every name of the taxonomy (DESIGN.md, `repro.obs`)
labels some op of the serving programs."""
import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.distributed import make_serve_fns
from repro.models import build_model
from repro.models.layers import use_tuned_layers

BATCH, PROMPT, GEN = 2, 16, 8
DECODE_NAMES = {"decode", "embed", "layer", "norm", "attn", "qkv", "rope",
                "core", "out", "cache_write", "ssm", "mix", "mlp", "head",
                "cast"}
PREFILL_NAMES = DECODE_NAMES - {"decode", "cache_write"} | {"prefill",
                                                            "kv_fill"}


def lowered(arch, tuned=False):
    """(prefill, decode step) of the smoke config, lowered; fresh
    closures each call, so nothing is served from a trace cache."""
    model = build_model(get_smoke(arch))
    params = model.abstract_params()
    prefill, decode = make_serve_fns(model)
    prefill = jax.jit(functools.partial(prefill, max_len=PROMPT + GEN))
    decode = jax.jit(decode, donate_argnums=(1,))
    batch = {"tokens": jax.ShapeDtypeStruct((BATCH, PROMPT), jnp.int32)}
    tok = jax.ShapeDtypeStruct((BATCH, 1), jnp.int32)
    with use_tuned_layers(tuned):
        lp = prefill.lower(params, batch)
        _, cache = jax.eval_shape(prefill, params, batch)
        ld = decode.lower(params, cache, tok)
    return lp, ld


def program(compiled) -> str:
    """Optimized HLO without metadata or the stack-frame tables, its
    instructions and computations renamed in order of appearance."""
    text = compiled.as_text().split("\n\nFileNames")[0]
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    names = {}
    return re.sub(r"%[A-Za-z0-9_.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


def op_paths(low) -> set:
    """The name path of every op in a lowered program (scan bodies'
    paths start inside the body)."""
    return set(re.findall(r'loc\("([^"]*)"\(', low.as_text(debug_info=True)))


def scope_names(low) -> set:
    return {part for path in op_paths(low) for part in path.split("/")}


@pytest.mark.parametrize("arch", ["hymba-1.5b", "starcoder2-3b",
                                  "moonlight-16b-a3b"])
@pytest.mark.parametrize("tuned", [False, True])
def test_scopes_leave_the_optimized_program_unchanged(arch, tuned,
                                                      monkeypatch):
    live = [program(low.compile()) for low in lowered(arch, tuned)]
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    off = [program(low.compile()) for low in lowered(arch, tuned)]
    assert live[0] == off[0], "prefill"
    assert live[1] == off[1], "decode step"


def test_every_scope_names_ops_of_the_serving_programs():
    lp, ld = lowered("hymba-1.5b")
    assert DECODE_NAMES <= scope_names(ld), DECODE_NAMES - scope_names(ld)
    assert PREFILL_NAMES <= scope_names(lp), PREFILL_NAMES - scope_names(lp)


def test_latent_attention_and_expert_scopes_name_ops():
    """Latent attention's steps in prefill (heads decompressed) and in
    decode (absorbed), and the expert layer's, each label some op."""
    lp, ld = lowered("moonlight-16b-a3b")

    def under(low, parent):
        """Scope names directly under ``parent``."""
        out = set()
        for path in op_paths(low):
            parts = path.split("/")
            out |= {b for a, b in zip(parts, parts[1:]) if a == parent}
        return out

    assert {"q", "kv_down", "kv_norm", "kv_up", "rope", "core",
            "out"} <= under(lp, "attn")
    assert {"q", "kv_down", "kv_norm", "rope", "cache_write", "absorb",
            "core", "unabsorb", "out"} <= under(ld, "attn")
    for low in (lp, ld):
        assert {"router", "group", "experts", "combine",
                "shared"} <= under(low, "mlp")


def test_kernels_are_scoped_by_kernel_id():
    lp, ld = lowered("hymba-1.5b", tuned=True)
    assert {"rms_norm", "mlp_matmul", "matmul"} <= scope_names(lp)
    assert {"rms_norm", "mlp_matmul", "matmul"} <= scope_names(ld)
    lp, _ = lowered("starcoder2-3b", tuned=True)
    assert "flash_attention" in scope_names(lp)


def test_weight_casts_are_named_under_their_layer():
    """Prefill converts each layer's float32 weights under the layer's
    scope; the decode step reads the bf16 copy that prefill hands over
    and converts none of them."""
    def layer_casts(low):
        casts = set()
        for path in op_paths(low):
            parts = path.split("/")
            if "layer" in parts and "cast" in parts:
                casts.add("/".join(parts[parts.index("layer") + 1:
                                         parts.index("cast")]))
        return casts

    lp, ld = lowered("hymba-1.5b")
    casts = layer_casts(lp)
    assert {"mlp", "attn/qkv", "attn/out", "ssm", "mix"} <= casts, casts
    assert layer_casts(ld) == set()
