"""Assigned input-shape sets and ``input_specs()``.

Every architecture pairs with four shapes (assignment):

* ``train_4k``    — seq 4096,   global batch 256  (lowers ``train_step``)
* ``prefill_32k`` — seq 32768,  global batch 32   (lowers ``prefill``)
* ``decode_32k``  — seq 32768,  global batch 128  (lowers ``serve_step``:
                    one new token against a KV cache of 32768)
* ``long_500k``   — seq 524288, global batch 1    (``serve_step``; only for
                    sub-quadratic archs — SSM / hybrid; full-attention archs
                    are skipped per assignment, see DESIGN.md §5)

``input_specs`` returns ``jax.ShapeDtypeStruct`` stand-ins (no allocation) —
the multi-pod dry-run lowers against these.  ``make_batch`` materializes
small concrete batches for smoke tests.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..models.lm import LMConfig, init_caches


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str        # "train" | "prefill" | "decode"
    seq: int
    batch: int


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}

SMOKE_SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 32, 2),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 48, 2),
    "decode_32k": ShapeSpec("decode_32k", "decode", 48, 2),
    "long_500k": ShapeSpec("long_500k", "decode", 64, 1),
}


def shape_applicable(cfg: LMConfig, shape_name: str) -> bool:
    """Assignment rule: ``long_500k`` only for sub-quadratic archs; prefill
    and decode only where every layer keeps a KV cache (not latent
    attention, not leading dense layers)."""
    if shape_name == "long_500k":
        return cfg.sub_quadratic
    if SHAPES[shape_name].kind in ("prefill", "decode"):
        return not (cfg.kv_lora_rank or cfg.first_k_dense)
    return True


def _token_batch_specs(cfg: LMConfig, batch: int, seq: int, with_loss: bool):
    i32 = jnp.int32
    cd = cfg.compute_dtype
    specs: Dict[str, jax.ShapeDtypeStruct] = {}
    if cfg.input_mode == "tokens":
        specs["tokens"] = jax.ShapeDtypeStruct((batch, seq), i32)
    elif cfg.input_mode == "embeds":
        specs["embeds"] = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cd)
    elif cfg.input_mode == "prefix_embeds":
        p = min(cfg.prefix_len, max(seq // 4, 1)) if seq <= 64 else cfg.prefix_len
        specs["prefix_embeds"] = jax.ShapeDtypeStruct((batch, p, cfg.d_model), cd)
        specs["tokens"] = jax.ShapeDtypeStruct((batch, seq - p), i32)
    if with_loss:
        specs["targets"] = jax.ShapeDtypeStruct((batch, seq), i32)
        specs["loss_mask"] = jax.ShapeDtypeStruct((batch, seq), jnp.float32)
    return specs


def cache_specs(cfg: LMConfig, batch: int, cache_len: int):
    return jax.eval_shape(lambda: init_caches(cfg, batch, cache_len))


def input_specs(cfg: LMConfig, shape: ShapeSpec) -> Dict:
    """ShapeDtypeStruct stand-ins for one (arch × shape) cell.

    train:    {"batch": {...}}
    prefill:  {"batch": {...}}                       (no loss tensors)
    decode:   {"batch": one-token, "caches": ..., "pos": scalar}
    """
    if shape.kind == "train":
        return {"batch": _token_batch_specs(cfg, shape.batch, shape.seq, True)}
    if shape.kind == "prefill":
        return {"batch": _token_batch_specs(cfg, shape.batch, shape.seq, False)}
    if shape.kind == "decode":
        if cfg.input_mode == "embeds":
            tok = {"embeds": jax.ShapeDtypeStruct((shape.batch, 1, cfg.d_model),
                                                  cfg.compute_dtype)}
        else:
            tok = {"tokens": jax.ShapeDtypeStruct((shape.batch, 1), jnp.int32)}
        return {
            "batch": tok,
            "caches": cache_specs(cfg, shape.batch, shape.seq),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }
    raise ValueError(shape.kind)


def make_batch(cfg: LMConfig, shape: ShapeSpec, seed: int = 0) -> Dict:
    """Concrete batch for smoke tests (small shapes only)."""
    rng = np.random.default_rng(seed)
    specs = input_specs(cfg, shape)

    def concretize(s: jax.ShapeDtypeStruct):
        if np.issubdtype(s.dtype, np.integer):
            hi = cfg.vocab_size if s.shape[-1:] != () else shape.seq
            return jnp.asarray(rng.integers(0, max(2, min(hi, cfg.vocab_size)),
                                            size=s.shape), dtype=s.dtype)
        if s.shape == ():
            return jnp.asarray(0, dtype=s.dtype)
        return jnp.asarray(rng.standard_normal(s.shape), dtype=s.dtype)

    out = jax.tree.map(concretize, specs)
    if "batch" in out and "loss_mask" in out["batch"]:
        out["batch"]["loss_mask"] = jnp.ones_like(out["batch"]["loss_mask"])
    if "caches" in out:
        # decode smoke: a real (zero) cache is semantically valid
        out["caches"] = init_caches(cfg, shape.batch, shape.seq)
        out["pos"] = jnp.asarray(min(4, shape.seq - 1), jnp.int32)
    return out
