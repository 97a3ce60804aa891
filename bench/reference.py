"""The plain reference: the configuration's forward pass in float32 at
``HIGHEST`` matmul precision, with its own RaZeR quantizers.  It imports
nothing of the program and takes nothing the program made: it regenerates
each layer's bf16 weights from the seed (``bench/weights.py``), quantizes and
dequantizes them itself, and runs layer by layer over a batch of token
sequences so that it fits beside nothing else on the chip.

What it computes, per the configuration (Qwen3 / DeepSeek-Coder, dense GQA):

    x = embed[t]
    per layer:  h = rms(x) * (1 + g1)
                q, k, v = h Wq, h Wk, h Wv          (W = RaZeR(W_bf16), dequantized)
                q, k = rms_head(q) * (1 + gq), rms_head(k) * (1 + gk)    (qk-norm only)
                q, k = rope(q), rope(k)             (rotate-half, theta from the config)
                k, v = KV(k), KV(v)                 (the 4.5-bit KV format, dequantized)
                x = x + causal_softmax(q k^T / sqrt(hd)) v Wo
                h = rms(x) * (1 + g2)
                x = x + (silu(h Wgate) * h Wup) Wdown
    logits = (rms(x) * (1 + gf)) lm_head^T

Every attention read goes through the KV format, as it does in a server
whose pages hold only wire bytes.

RaZeR weights (the paper's Eq. 1-3, 6-7): blocks of 16 along the input
dimension; a float32 tensor scale amax / (30 * 6); per block an E3M3 scale,
the nearest grid value to amax / (tensor_scale * 6) (a zero scale of a
nonzero block becomes the smallest one); elements rounded to FP4 E2M1, or
to FP4 with one special value of {+5, -5, +8, -8} put in place of -0,
whichever has the smallest squared error (ties keep the earlier candidate).
Rounding to a grid takes the nearest value, the lower one on a tie.  The KV
format is the same per (token, head) block of 16 with an E4M3 scale, no
tensor scale, and special values {+5, -5}.

``control=True`` is the same pass with every linear layer's input rounded
to float8 E4M3 (per-row scale to the format's range): the next precision
below the configuration's bf16 activations.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _minifloat(n_exp: int, n_man: int, drop_top: bool = False) -> np.ndarray:
    bias = 2 ** (n_exp - 1) - 1
    vals = {0.0}
    for e in range(2 ** n_exp):
        for m in range(2 ** n_man):
            if e == 0:
                vals.add(2.0 ** (1 - bias) * m / 2 ** n_man)
            else:
                vals.add(2.0 ** (e - bias) * (1 + m / 2 ** n_man))
    out = np.array(sorted(vals), np.float32)
    return out[:-1] if drop_top else out


E2M1 = _minifloat(2, 1)                     # 0 .. 6
E3M3 = _minifloat(3, 3)                     # 0 .. 30
E4M3 = _minifloat(4, 3, drop_top=True)      # 0 .. 448 (OCP: 480 is NaN)
FP4 = np.concatenate([-E2M1[:0:-1], E2M1])  # signed, one zero
WEIGHT_SVS = (5.0, -5.0, 8.0, -8.0)
KV_SVS = (5.0, -5.0)


def round_to(x, grid: np.ndarray):
    """Nearest value of the sorted ``grid``; on a tie the lower one."""
    mids = (grid[1:] + grid[:-1]) / 2
    idx = sum((x > float(m)).astype(jnp.int32) for m in mids)
    return jnp.asarray(grid)[idx]


def _block_qdq(xb, tensor_scale, scale_grid, svs):
    """xb (..., nblk, 16) f32 -> dequantized values of the same shape."""
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scale = round_to(amax / (tensor_scale * 6.0), scale_grid)
    scale = jnp.where((scale == 0) & (amax > 0), float(scale_grid[1]), scale)
    denom = (tensor_scale * scale)[..., None]
    y = xb / jnp.where(denom == 0, 1.0, denom)
    best = round_to(y, FP4)
    err = jnp.sum((best - y) ** 2, axis=-1)
    for v in svs:
        grid = np.sort(np.concatenate([FP4[FP4 != 0], [0.0, v]]).astype(np.float32))
        q = round_to(y, grid)
        e = jnp.sum((q - y) ** 2, axis=-1)
        take = e < err
        best = jnp.where(take[..., None], q, best)
        err = jnp.where(take, e, err)
    return best * denom


def razer_weight(w):
    """(K, N) weight -> its RaZeR value, f32, blocked along K."""
    k, n = w.shape
    w = w.astype(F32)
    ts = jnp.max(jnp.abs(w)) / (float(E3M3[-1]) * 6.0)
    ts = jnp.where(ts == 0, 1.0, ts)
    xb = w.T.reshape(n, k // 16, 16)
    return _block_qdq(xb, ts, E3M3, WEIGHT_SVS).reshape(n, k).T


def kv_format(x):
    """(..., hd) keys or values -> their 4.5-bit KV value, f32."""
    hd = x.shape[-1]
    xb = x.astype(F32).reshape(*x.shape[:-1], hd // 16, 16)
    return _block_qdq(xb, jnp.float32(1.0), E4M3, KV_SVS).reshape(x.shape)


def fp8(x):
    """Round to float8 E4M3 with a per-row scale (the control's activations)."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g.astype(F32))


def rope(x, theta: float):
    """Rotate-half RoPE over positions 0..S-1 of x (B, S, H, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _layer_weights(key, cfg_items, i):
    cfg = dict(cfg_items)
    lw = weights.layer(key, i, cfg)
    return {n: (razer_weight(v) if n in weights.MATRICES else v.astype(F32))
            for n, v in lw.items()}


def _attention(q, k, v, q_chunk: int):
    """Causal GQA attention, f32, in query chunks. q (B,S,H,hd), k/v (B,S,KVH,hd)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qc = min(q_chunk, s)
    qs = q.reshape(b, s // qc, qc, kvh, g, hd).transpose(1, 0, 3, 4, 2, 5)
    kpos = jnp.arange(s)

    def chunk(args):
        ci, qb = args  # qb (B, KVH, G, qc, hd)
        sc = jnp.einsum("bkgqd,bskd->bkgqs", qb, k, precision=HI) / np.sqrt(hd)
        qpos = ci * qc + jnp.arange(qc)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bkgqs,bskd->bkgqd", p, v, precision=HI)

    out = jax.lax.map(chunk, (jnp.arange(s // qc), qs))  # (nc, B, KVH, G, qc, hd)
    return out.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, h * hd)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _layer(x, w, cfg_items, control: bool):
    cfg = dict(cfg_items)
    b, s, _ = x.shape
    hd, h, kvh = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    act = fp8 if control else (lambda a: a)

    def mm(a, wname):
        return jnp.einsum("bsk,kn->bsn", act(a), w[wname], precision=HI)

    hx = rms(x, w["ln1"], eps)
    q = mm(hx, "wq").reshape(b, s, h, hd)
    k = mm(hx, "wk").reshape(b, s, kvh, hd)
    v = mm(hx, "wv").reshape(b, s, kvh, hd)
    if cfg["qk_norm"]:
        q = rms(q, w["q_norm"], eps)
        k = rms(k, w["k_norm"], eps)
    q = rope(q, cfg["rope_theta"])
    k = kv_format(rope(k, cfg["rope_theta"]))
    v = kv_format(v)
    x = x + mm(_attention(q, k, v, 256), "wo")
    h2 = rms(x, w["ln2"], eps)
    m = jax.nn.silu(mm(h2, "gate")) * mm(h2, "up")
    return x + mm(m, "down")


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, rows, cols, final_norm, lm_head, eps: float, control: bool):
    hsel = rms(x[rows, cols], final_norm, eps)
    if control:
        hsel = fp8(hsel)
    return jnp.einsum("nd,vd->nv", hsel, lm_head.astype(F32), precision=HI)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


def _cfg_items(cfg: Dict) -> Tuple:
    keys = ("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "vocab_size", "rms_norm_eps", "rope_theta", "qk_norm")
    return tuple((k, cfg[k]) for k in keys)


def logits_at(cfg: Dict, seed: int, seqs: Sequence[Sequence[int]],
              positions: Sequence[Sequence[int]], *, rows: int, length: int,
              control: bool = False):
    """Reference logits (f32, numpy) at ``positions[i]`` of each sequence,
    stacked in order.  With ``control`` also the control's: (ref, ctl).
    The batch is padded to ``rows`` sequences of ``length`` tokens, so every
    run compiles the same shapes."""
    key = weights.seed_key(seed)
    items = _cfg_items(cfg)
    s = -(-length // 256) * 256
    if len(seqs) > rows or max(len(t) for t in seqs) > s:
        raise ValueError(f"{len(seqs)} sequences of up to {max(len(t) for t in seqs)} "
                         f"tokens do not fit {rows} x {s}")
    toks = np.zeros((rows, s), np.int32)
    for i, t in enumerate(seqs):
        toks[i, : len(t)] = t
    rows = np.concatenate([np.full(len(p), i) for i, p in enumerate(positions)])
    cols = np.concatenate([np.asarray(p) for p in positions])
    outer = weights.outer(key, cfg)
    x = _embed(outer["embed"], jnp.asarray(toks))
    xc = x if control else None
    for i in range(cfg["num_hidden_layers"]):
        w = _layer_weights(key, items, i)
        x = _layer(x, w, items, False)
        if control:
            xc = _layer(xc, w, items, True)
        del w
    eps = cfg["rms_norm_eps"]
    ref = np.asarray(_head(x, rows, cols, outer["final_norm"], outer["lm_head"], eps, False))
    if not control:
        return ref
    ctl = np.asarray(_head(xc, rows, cols, outer["final_norm"], outer["lm_head"], eps, True))
    return ref, ctl


def served_gaps(ref_logits: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """How far each served token's reference logit lies below the best one."""
    tokens = np.asarray(tokens)
    return ref_logits.max(axis=-1) - ref_logits[np.arange(len(tokens)), tokens]


def teacher_forced(requests) -> Tuple[List[List[int]], List[List[int]], List[int]]:
    """For served requests (prompt, out_tokens): the sequences to run, the
    positions whose logits chose each served token, and the tokens."""
    seqs, pos, toks = [], [], []
    for prompt, out in requests:
        p = len(prompt)
        seqs.append(list(prompt) + list(out[:-1]))
        pos.append(list(range(p - 1, p - 1 + len(out))))
        toks.extend(out)
    return seqs, pos, toks
