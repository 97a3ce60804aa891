"""Analytic cost model + TPU v5e hardware constants for the roofline.

MODEL_FLOPS follows the brief: 6*N*D for training (N = params, D = tokens),
6*N_active*D for MoE; serve steps use the 2*N(*_active)*D inference form.
Attention/recompute overheads are intentionally NOT in MODEL_FLOPS -- the
MODEL/HLO ratio surfaces them (remat policy costs ~1 extra forward => ~0.75
for train).

Param counts come from the real param tree (eval_shape), not hand formulas.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import numpy as np

from repro.models import transformer as tf
from repro.models.config import ArchConfig

# TPU v5e per chip (brief-mandated constants)
PEAK_FLOPS = 197e12  # bf16 FLOP/s
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link


def param_counts(cfg: ArchConfig) -> Dict[str, float]:
    """(total, expert, non_expert, active) parameter counts from the tree."""
    shapes = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    total = expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = int(np.prod(leaf.shape))
        total += n
        if any("experts" == str(getattr(k, "key", k)) for k in path):
            expert += n
    non_expert = total - expert
    if cfg.moe and cfg.n_experts:
        active = non_expert + expert * cfg.topk / cfg.n_experts
    else:
        active = total
    return {"total": float(total), "expert": float(expert), "active": float(active)}


def model_flops(cfg: ArchConfig, seq_len: int, global_batch: int, kind: str) -> float:
    """Brief formula: train 6*N_active*D; prefill 2*N_active*D; decode
    2*N_active*B (one token per sequence)."""
    pc = param_counts(cfg)
    n_active = pc["active"]
    if kind == "train":
        return 6.0 * n_active * seq_len * global_batch
    if kind == "prefill":
        return 2.0 * n_active * seq_len * global_batch
    if kind == "decode":
        return 2.0 * n_active * global_batch
    raise ValueError(kind)


def roofline_terms(
    hlo_flops_per_dev: float,
    hlo_bytes_per_dev: float,
    coll_bytes_per_dev: float,
    n_links: int = 4,  # v5e: 4 ICI links per chip (2D torus, 2 axes x 2 dirs)
) -> Dict[str, float]:
    return {
        "compute_s": hlo_flops_per_dev / PEAK_FLOPS,
        "memory_s": hlo_bytes_per_dev / HBM_BW,
        "collective_s": coll_bytes_per_dev / (ICI_BW * n_links),
    }


def dominant(terms: Dict[str, float]) -> str:
    return max(("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
