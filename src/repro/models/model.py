"""Unified model facade: one object per architecture config exposing
init / loss / prefill / decode_step / init_cache / input shapes /
MODEL_FLOPS accounting, independent of family.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import Sharder
from repro.models.config import LM_SHAPES, ModelConfig, ShapeSpec
from repro.models import encdec as ed
from repro.models import transformer as tf

__all__ = ["Model", "build_model", "batch_shapes"]


def batch_shapes(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStructs for one input batch of the given shape spec."""
    b, s = shape.global_batch, shape.seq_len
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    if shape.kind == "train":
        out["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        if cfg.frontend == "frames":
            out["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.enc_seq, cfg.d_model), jnp.dtype(cfg.dtype))
    elif shape.kind == "prefill":
        out["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        if cfg.frontend == "frames":
            out["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.enc_seq, cfg.d_model), jnp.dtype(cfg.dtype))
    elif shape.kind == "decode":
        out["token"] = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    else:
        raise ValueError(shape.kind)
    return out


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # -- params ---------------------------------------------------------------
    def init(self, key) -> Dict:
        if self.cfg.family == "encdec":
            return ed.init_encdec(key, self.cfg)
        return tf.init_lm(key, self.cfg)

    def abstract_params(self, key=None) -> Dict:
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(lambda k: self.init(k), key)

    # -- training -------------------------------------------------------------
    def loss(self, params: Dict, batch: Dict, shd: Sharder
             ) -> Tuple[jax.Array, Dict]:
        if self.cfg.family == "encdec":
            return ed.encdec_loss(params, batch, self.cfg, shd)
        return tf.lm_loss(params, batch, self.cfg, shd)

    # -- serving --------------------------------------------------------------
    def prefill(self, params: Dict, batch: Dict, shd: Sharder,
                max_len: int = 0):
        if self.cfg.family == "encdec":
            return ed.encdec_prefill(params, batch["frames"],
                                     batch["tokens"], self.cfg, shd,
                                     max_len=max_len)
        return tf.lm_prefill(params, batch["tokens"], self.cfg, shd,
                             max_len=max_len,
                             inputs_embeds=batch.get("frames"))

    def decode_step(self, params: Dict, cache: Dict, token: jax.Array,
                    shd: Sharder):
        if self.cfg.family == "encdec":
            return ed.encdec_decode_step(params, cache, token, self.cfg,
                                         shd)
        return tf.lm_decode_step(params, cache, token, self.cfg, shd)

    def init_cache(self, batch: int, seq_len: int,
                   params: Optional[Dict] = None) -> Dict:
        """An empty decode state; given ``params``, with what prefill
        derives from them (the compute-precision layer weights)."""
        if self.cfg.family == "encdec":
            return ed.init_encdec_cache(self.cfg, batch, seq_len)
        return tf.init_lm_cache(self.cfg, batch, seq_len, params=params)

    def abstract_cache(self, batch: int, seq_len: int,
                       params: Optional[Dict] = None) -> Dict:
        """The decode state's shapes for ``params`` (by default
        `abstract_params`), as prefill returns it."""
        if params is None:
            params = self.abstract_params()
        return jax.eval_shape(
            lambda p: self.init_cache(batch, seq_len, p), params)

    # -- accounting -----------------------------------------------------------
    def model_flops(self, shape: ShapeSpec) -> float:
        """MODEL_FLOPS per the assignment: 6·N·D (dense) / 6·N_active·D
        (MoE) for training; 2·N·D per generated/processed token for
        inference shapes."""
        n_active = self.cfg.num_active_params()
        if shape.kind == "train":
            tokens = shape.global_batch * shape.seq_len
            return 6.0 * n_active * tokens
        if shape.kind == "prefill":
            tokens = shape.global_batch * shape.seq_len
            return 2.0 * n_active * tokens
        # decode: one token per sequence per step
        return 2.0 * n_active * shape.global_batch

    def supports_shape(self, shape: ShapeSpec) -> Tuple[bool, str]:
        """long_500k requires sub-quadratic sequence mixing (DESIGN.md)."""
        if shape.name == "long_500k" and self.cfg.family not in (
                "ssm", "hybrid"):
            return False, ("skip: full-attention arch at 524k decode "
                           "(quadratic KV) — per assignment/DESIGN.md")
        return True, ""


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg=cfg)
