"""Operations and bytes the served model needs, from its shapes alone.

The counts are of the work the algorithm requires, not of what a kernel
happens to do: a decode step needs every packed weight, the lm_head and the
live tokens' KV bytes once, whatever the kernels read; padded prompt
positions and idle decode slots are not work.  A 4.5-bit (K, N) weight is
``K*N/2`` code bytes plus ``K*N/16`` scale bytes.
"""
from __future__ import annotations

from typing import Dict, Iterable

from bench.weights import matrix_shapes


def kv_bytes_per_token(cfg: Dict) -> int:
    """Wire bytes of one token's K and V in one layer."""
    hd = cfg["head_dim"]
    return 2 * cfg["num_key_value_heads"] * (hd // 2 + hd // 16)


def packed_bytes(k: int, n: int) -> int:
    return k * n // 2 + k * n // 16


def layer_params(cfg: Dict) -> int:
    return sum(k * n for k, n in matrix_shapes(cfg).values())


def matmul_call(m: int, k: int, n: int):
    """One packed projection y[M,N] = x[M,K] W[K,N]: (flops, bytes) with
    bf16 activations in and out."""
    return 2 * m * k * n, packed_bytes(k, n) + 2 * m * k + 2 * m * n


def attention_flops(cfg: Dict, q_positions: Iterable[int]) -> int:
    """QK^T and PV over the positions each query attends (itself included):
    ``q_positions`` are the attended lengths, one per query token."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return 4 * h * hd * sum(q_positions) * cfg["num_hidden_layers"]


def decode_attention_bytes(cfg: Dict, live_lens: Iterable[int]) -> int:
    """KV wire bytes of the live tokens one decode step attends, all layers."""
    return kv_bytes_per_token(cfg) * sum(live_lens) * cfg["num_hidden_layers"]


def decode_steps(cfg: Dict, steps: int, tokens: int, attended: int):
    """(flops, bytes) that ``steps`` decode steps need, which made ``tokens``
    tokens attending ``attended`` positions in all (each counting the token
    being written): every packed weight and the lm_head once per step, the
    live KV bytes once, the FLOPs of the live slots only."""
    d, v, layers = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    flops = 2 * tokens * (layer_params(cfg) * layers + v * d)
    flops += attention_flops(cfg, [attended])
    wbytes = sum(packed_bytes(k, n) for k, n in matrix_shapes(cfg).values())
    nbytes = steps * (wbytes * layers + 2 * v * d) + decode_attention_bytes(cfg, [attended])
    return flops, nbytes


def prefill(cfg: Dict, computed: int, cached: int) -> int:
    """Model FLOPs of prefilling ``computed`` prompt tokens after ``cached``
    ones: the projections, causal attention over cached + earlier tokens,
    and the lm_head at the last position."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    flops = 2 * computed * layer_params(cfg) * cfg["num_hidden_layers"] + 2 * v * d
    return flops + attention_flops(cfg, range(cached + 1, cached + computed + 1))
