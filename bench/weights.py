"""Seeded bf16 weights, made by the benchmark: the system under test packs
them, and the reference regenerates them layer by layer.  Nothing here
imports the program.

Layer ``i``'s leaves come from ``fold_in(key(seed), i)``, so a layer can be
rebuilt alone.  Matrices are (fan_in, fan_out) with entries N(0, 1/fan_in);
embed and lm_head N(0, 0.02^2); every norm gain is ``1 + g`` with g drawn
N(0, 0.1^2), so a norm that is skipped or applied twice shows.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16

# leaf name -> (fan_in, fan_out) as a function of the configuration
MATRICES = ("wq", "wk", "wv", "wo", "gate", "up", "down")


def matrix_shapes(cfg: Dict) -> Dict[str, tuple]:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kvh, ff = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["intermediate_size"]
    return {"wq": (d, h * hd), "wk": (d, kvh * hd), "wv": (d, kvh * hd),
            "wo": (h * hd, d), "gate": (d, ff), "up": (d, ff), "down": (ff, d)}


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(BF16)


def layer_keys(seed_key, i):
    """The keys of layer ``i``: one per matrix, then the norm gains'."""
    return jax.random.split(jax.random.fold_in(seed_key, i), len(MATRICES) + 4)


def matrix(keys, name: str, cfg: Dict) -> jax.Array:
    shp = matrix_shapes(cfg)[name]
    return _normal(keys[MATRICES.index(name)], shp, shp[0] ** -0.5)


def gains(keys, cfg: Dict) -> Dict[str, jax.Array]:
    """``ln1``, ``ln2`` and, with qk-norm, ``q_norm``/``k_norm`` as ``g``,
    applied as ``1 + g``."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    out = {"ln1": _normal(keys[7], (d,), 0.1), "ln2": _normal(keys[8], (d,), 0.1)}
    if cfg["qk_norm"]:
        out["q_norm"] = _normal(keys[9], (hd,), 0.1)
        out["k_norm"] = _normal(keys[10], (hd,), 0.1)
    return out


def layer(seed_key, i, cfg: Dict) -> Dict[str, jax.Array]:
    """Layer ``i``'s bf16 leaves: the seven matrices and the norm gains."""
    keys = layer_keys(seed_key, i)
    out = {name: matrix(keys, name, cfg) for name in MATRICES}
    out.update(gains(keys, cfg))
    return out


def outer(seed_key, cfg: Dict) -> Dict[str, jax.Array]:
    """embed (V, d), lm_head (V, d) and the final norm gain, bf16."""
    k = jax.random.split(jax.random.fold_in(seed_key, 1 << 20), 3)
    v, d = cfg["vocab_size"], cfg["hidden_size"]
    return {"embed": _normal(k[0], (v, d), 0.02),
            "lm_head": _normal(k[1], (v, d), 0.02),
            "final_norm": _normal(k[2], (d,), 0.1)}


def seed_key(seed: int):
    # seeds may exceed 32 bits: fold the high word in
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
