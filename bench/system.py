"""The system under test, as the benchmark drives it: the program's
``Engine.serve`` with RaZeR-packed weights, the 4.5-bit paged KV pool, the
prefix cache and the scheduler at the configuration's slots.

Weights are packed on the device in one jitted call from the seed: each
layer's bf16 matrices (``bench/weights.py``) go through the packer of
``QuantPolicy.packed()`` inside a ``lax.map`` over layers, whose stacked
output is the engine's scan-stacked tree.  ``Engine`` would pack a bf16 tree
itself, but it keeps that tree alive beside the packed one, and a whole bf16
Qwen3-8B does not fit one chip.  So the engine is handed the packed tree,
under the same policy with one rule added (``layers_*`` resolve to no spec)
that stops ``pack_model_weights`` from walking the already packed layers.
"""
from __future__ import annotations

import sys
from pathlib import Path
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import weights  # noqa: E402

GROUP = "layers_0"  # every layer of these dense models is one scan group


def arch_config(cfg: Dict):
    from repro.models.config import ArchConfig

    return ArchConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], qk_norm=cfg["qk_norm"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        compute_dtype="bfloat16", param_dtype="bfloat16")


def serve_policy():
    """The deployment policy: packed RaZeR weights and the RaZeR KV pool."""
    from repro.core.policy import QuantPolicy

    return QuantPolicy.packed(kv_quant=True)


def _path(name: str) -> str:
    return f"{GROUP}/{'mlp' if name in ('gate', 'up', 'down') else 'mixer'}/{name}"


# elements per call of the packer: its temporaries are about ten float32
# copies of what it is given, so DeepSeek-Coder's 7168 x 19200 matrices go in
# column slices (2.2 GB of temporaries each instead of 6.3 GB whole)
PACK_CHUNK = 1 << 26


def pack(spec, w, chunk: int = PACK_CHUNK):
    """``spec.pack(w)``, bit for bit, a slice of columns at a time.

    The format blocks each column on its own and scales the whole matrix by
    one tensor scale, its largest magnitude over 180.  Each slice is packed
    with the column that holds that magnitude appended, so every slice gets
    the whole matrix's tensor scale, and the appended column is dropped."""
    from repro.core.packing import PackedRazerWeight

    k, n = w.shape
    parts = -(-(k * n) // chunk)
    while parts > 1 and (n % parts or (n // parts) % 128):
        parts += 1
    if parts <= 1 or parts > n // 128:
        return spec.pack(w)
    nc = n // parts
    top = jax.lax.dynamic_slice_in_dim(w, jnp.argmax(jnp.max(jnp.abs(w), axis=0)), 1, axis=1)
    slices = w.reshape(k, parts, nc).transpose(1, 0, 2)
    got = jax.lax.map(lambda ws: spec.pack(jnp.concatenate([ws, top], axis=1)), slices)

    def join(a):  # (parts, rows, nc + 1) -> (rows, n)
        return a[:, :, :nc].transpose(1, 0, 2).reshape(a.shape[1], n)

    return PackedRazerWeight(join(got.codes), join(got.scale_meta), got.tensor_scale[0],
                             got.sv_magnitudes, (k, n))


def make_params(cfg: Dict, seed: int):
    """The engine's param tree, packed on the device in one jitted call."""
    return packed_params_fn(cfg)(weights.seed_key(seed))


def packed_params_fn(cfg: Dict):
    """The jitted ``key -> param tree`` that ``make_params`` calls."""
    policy = serve_policy()
    specs = {name: policy.resolve(_path(name)) for name in weights.MATRICES}
    for name, spec in specs.items():
        if spec is None or spec.mode != "packed":
            raise RuntimeError(f"{_path(name)} does not resolve to a packed spec")

    @jax.jit
    def build(key):
        def one(i):
            keys = weights.layer_keys(key, i)
            packed = {}
            for name in weights.MATRICES:
                k = keys
                if packed:
                    # one matrix's quantizer temporaries at a time: the next
                    # matrix is drawn only once the previous one is packed
                    packed, k = jax.lax.optimization_barrier((packed, keys))
                w = weights.matrix(k, name, cfg).astype(jnp.float32)
                packed[name] = pack(specs[name], w)
            g = weights.gains(keys, cfg)
            p = {"ln1": g.pop("ln1"), "ln2": g.pop("ln2"),
                 "mixer": {n: packed[n] for n in ("wq", "wk", "wv", "wo")},
                 "mlp": {n: packed[n] for n in ("gate", "up", "down")}}
            p["mixer"].update(g)
            return p

        layers = jax.lax.map(one, jnp.arange(cfg["num_hidden_layers"]))
        out = weights.outer(key, cfg)
        out[GROUP] = layers
        return out

    return build


def make_engine(cfg: Dict, params):
    from repro.core.policy import LayerRule
    from repro.serving.engine import Engine, ServeConfig

    sv = cfg["serving"]
    policy = serve_policy().with_rules(LayerRule.dense(f"{GROUP}*"))
    scfg = ServeConfig(max_len=sv["max_len"], max_new_tokens=1, kv_quant=True,
                       quant=policy, eos_id=-1)
    return Engine(params, arch_config(cfg), scfg)


def sched_and_pool(cfg: Dict):
    from repro.serving.pagepool import PagePoolConfig
    from repro.serving.scheduler import SchedulerConfig

    sv = cfg["serving"]
    return (SchedulerConfig(max_slots=sv["max_slots"],
                            prefill_token_budget=sv["prefill_token_budget"]),
            PagePoolConfig(num_pages=sv["num_pages"], page_size=sv["page_size"],
                           max_len=sv["max_len"]))


def pool_use(registry, reqs, page_size: int) -> Dict[str, int]:
    """Pages of the pool in use, from the program's own ``pool_pages`` gauge
    in the registry ``serve`` was given, and what the requests still running
    need: the pages their tokens fill, and the worst case admission reserved."""
    gauge = registry.get("pool_pages")
    state = lambda s: int(gauge.value(stage="engine", replica="0", state=s))  # noqa: E731
    live = [r for r in reqs if r.prefill_start is not None and r.finish_time is None]
    pages = lambda n: -(-n // page_size)  # noqa: E731
    return {"pages": state("live") + state("free"), "in_use": state("live"),
            "live_requests": len(live),
            "filled_by_live": sum(pages(len(r.prompt) + len(r.out_tokens)) for r in live),
            "reserved_by_live": sum(pages(len(r.prompt) + r.max_new_tokens) for r in live)}


def requests(planned) -> List:
    """The program's ``Request`` objects for the generator's plan."""
    from repro.serving.scheduler import Request

    return [Request(rid=p.rid, prompt=list(p.prompt), max_new_tokens=p.max_new_tokens,
                    arrival=p.arrival, eos_id=-1) for p in planned]


def serve(engine, cfg: Dict, reqs, *, clock, trace=None, metrics=None):
    sched_cfg, pool_cfg = sched_and_pool(cfg)
    return engine.serve(reqs, sched_cfg=sched_cfg, pool_cfg=pool_cfg,
                        prefix_cache=True, clock=clock, trace=trace, metrics=metrics)
