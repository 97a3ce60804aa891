"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3_2_3b --reduced \
        --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/run1

On a real TPU pod this binds the production mesh and shards per
parallel.sharding; on this CPU container use --reduced (or it will try to
allocate the full model).  Restarts resume from the newest checkpoint.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.policy import QuantPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf
from repro.parallel.sharding import param_sharding_tree, sharding_ctx
from repro.train.checkpoint import CheckpointManager, latest_step, restore_checkpoint
from repro.train.data import DataConfig, SyntheticLM
from repro.train.fault import ResilientLoop, StragglerPolicy
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="none", choices=["none", "single", "multi"],
                    help="production mesh binding (TPU pods); 'none' = local devices")
    ap.add_argument("--qat", action="store_true", help="RaZeR fake-quant QAT forward")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = None
    if args.mesh != "none":
        from repro.launch.mesh import make_production_mesh

        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    quant = QuantPolicy.fakequant(ste=True) if args.qat else QuantPolicy.bf16()
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1), total_steps=args.steps)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch)
    ds = SyntheticLM(dcfg)

    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    opt = init_opt_state(params)
    if mesh is not None:
        shardings = param_sharding_tree(params, mesh)
        params = jax.device_put(params, shardings)

    @jax.jit
    def train_step(params, opt, tokens, labels):
        with sharding_ctx(mesh):
            (loss, m), g = jax.value_and_grad(
                lambda p: tf.lm_loss(p, {"tokens": tokens, "labels": labels}, cfg, quant),
                has_aux=True,
            )(params)
            params, opt, om = adamw_update(params, g, opt, ocfg)
            return params, opt, loss, dict(m, **om)

    state = {"params": params, "opt": opt}
    ckpt_dir = args.ckpt_dir or f"/tmp/razer_{args.arch}_ckpt"
    mgr = CheckpointManager(ckpt_dir, every=args.ckpt_every)
    start = latest_step(ckpt_dir) or 0
    if start:
        state, start = restore_checkpoint(ckpt_dir, state)
        print(f"resumed from step {start}")

    t_last = time.monotonic()

    def step_fn(state, step):
        nonlocal t_last
        b = ds.batch(step)
        p, o, loss, m = train_step(state["params"], state["opt"],
                                   jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
        if step % 10 == 0:
            dt = time.monotonic() - t_last
            t_last = time.monotonic()
            print(f"step {step:5d} loss {float(loss):.4f} "
                  f"lr {float(m['lr']):.2e} gnorm {float(m['grad_norm']):.2f} ({dt:.2f}s/10)")
        return {"params": p, "opt": o}

    loop = ResilientLoop(mgr, straggler=StragglerPolicy())
    state, end = loop.run(state, step_fn, start_step=start, num_steps=args.steps - start)
    mgr.maybe_save(end, state, force=True)
    mgr.wait()
    print(f"done at step {end}; checkpoints in {ckpt_dir}")


if __name__ == "__main__":
    main()
