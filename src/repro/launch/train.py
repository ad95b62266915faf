"""Training launcher: ``python -m repro.launch.train --arch <id> [--smoke]``.

Production path: builds the pjit train step for the selected architecture
under the production mesh (on a real TPU slice the same code runs unchanged;
on this CPU container use ``--smoke`` for a reduced config on one device).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_config, get_shape, smoke_config, smoke_shape
from repro.configs.base import ShapeConfig
from repro.data import PipelineConfig, make_batch
from repro.distributed.sharding import ShardingRules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_train_step
from repro.models import ExecConfig, build_model
from repro.optim import SGD, AdamW, warmup_cosine
from repro.telemetry import clock as tclock
from repro.telemetry import metrics as tmetrics
from repro.telemetry import spans as tspans


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config, single device, tiny batch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--optimizer", choices=["sgd", "adamw"], default="sgd")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default="artifacts/train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        cfg = smoke_config(args.arch)
        shape = smoke_shape("train")
        mesh = None
        ec = ExecConfig(backend="xla", loss_chunk=16)
    else:
        cfg = get_config(args.arch)
        shape = get_shape(args.shape)
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        ec = ExecConfig(backend="auto", loss_chunk=512)

    model = build_model(cfg, ec)
    sched = warmup_cosine(args.lr, warmup=max(1, args.steps // 10),
                          total=args.steps)
    opt = SGD(lr=sched) if args.optimizer == "sgd" else AdamW(lr=sched)
    ck = Checkpointer(args.ckpt_dir, keep=2)

    print(f"train {cfg.name}: {cfg.param_count() / 1e6:.1f}M params, "
          f"{shape.name}, opt={args.optimizer}")

    if mesh is not None:
        rules = ShardingRules(mesh, cfg)
        with mesh:
            step_fn, _ = make_train_step(model, opt, rules, shape)
    else:
        def raw_step(params, state, batch):
            (loss, m), grads = jax.value_and_grad(model.loss, has_aux=True)(
                params, batch)
            params, state = opt.update(grads, state, params)
            m = dict(m, loss=loss)
            return params, state, m
        step_fn = jax.jit(raw_step)

    params = model.init(jax.random.PRNGKey(0))
    state = opt.init(params)
    start = 0
    if args.resume and ck.latest_step() is not None:
        (params, state), start, _ = ck.restore((params, state))
        print(f"resumed at step {start}")

    pc = PipelineConfig(seed=0)
    # step timing flows through the telemetry registry; the printed log
    # reads the histogram back, so it and any scrape agree by construction
    hist = tmetrics.registry().histogram("faasm_train_step_ms")
    tel = tspans.tracer()
    for step in range(start, args.steps):
        s0 = tclock.now()
        batch = {k: jnp.asarray(v)
                 for k, v in make_batch(cfg, shape, pc, step).items()}
        params, state, metrics = step_fn(params, state, batch)
        s1 = tclock.now()
        hist.observe((s1 - s0) * 1e3)
        if tel is not None:
            tel.record("train.step", "train", s0, s1, step=step)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):8.4f} "
                  f"gnorm {float(metrics.get('grad_norm', 0.0)):8.3f} "
                  f"({hist.sum / 1e3:6.1f}s, "
                  f"p50 {hist.percentile(0.5):5.0f}ms)")
        if args.ckpt_every and step and step % args.ckpt_every == 0:
            ck.save(step, (params, state))
    ck.save(args.steps, (params, state), blocking=True)
    print("done")


if __name__ == "__main__":
    main()
