"""Training launcher: --arch <id> [--reduced] with the fault-tolerant loop
and always-on coreset selection in the input pipeline.

On this CPU container it runs reduced configs end-to-end (examples/ use it);
on a real cluster the same entrypoint runs the full config on the
production mesh — the jitted step is the exact function the dry-run lowers.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.ckpt import CheckpointStore
from repro.configs import all_archs, get_config
from repro.data import CoresetSelector, TokenStreamSpec, deterministic_batch_fn
from repro.launch import sharding as shd
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import Model
from repro.train import AdamWConfig, TrainStepConfig, init_opt_state, \
    make_train_step
from repro.train.loop import LoopConfig, run_training


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=all_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--coreset-k", type=int, default=0,
                    help="if >0, run ThreeSieves coreset selection over "
                         "per-example embeddings in the input pipeline")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the (16,16) mesh (needs 256 devices)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    model = Model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    rules = shd.build_rules(cfg, mesh)
    param_sh = shd.shardings(model.spec(), rules, mesh)

    key = jax.random.PRNGKey(0)
    with jax.sharding.set_mesh(mesh):
        params = jax.jit(model.init, out_shardings=param_sh)(key)
        opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps)
        opt_state = init_opt_state(params, opt_cfg)
        step_cfg = TrainStepConfig(num_microbatches=args.microbatches)
        train_step = jax.jit(make_train_step(model, opt_cfg, step_cfg))

        spec = TokenStreamSpec(vocab=cfg.vocab, seq=args.seq,
                               batch=args.batch)
        base_fn = deterministic_batch_fn(0, spec)

        selector = None
        if args.coreset_k:
            selector = CoresetSelector(K=args.coreset_k, d=cfg.d_model,
                                       T=500, eps=0.01)

        def next_batch(step):
            b = base_fn(step)
            if cfg.encoder is not None:
                b["frames"] = jnp.zeros(
                    (args.batch, cfg.encoder.n_frames, cfg.d_model),
                    cfg.activation_dtype)
            if cfg.n_prefix:
                b["prefix"] = jnp.zeros(
                    (args.batch, cfg.n_prefix, cfg.d_model),
                    cfg.activation_dtype)
            if selector is not None:
                # cheap diversity embedding: folded token histogram — stands
                # in for the embedding-table mean a production pipeline uses
                hist = jax.nn.one_hot(b["tokens"] % 64, 64).mean(1)
                selector.update(hist)
            return b

        store = CheckpointStore(args.ckpt_dir)
        loop_cfg = LoopConfig(total_steps=args.steps,
                              ckpt_every=args.ckpt_every)
        params, opt_state, report = run_training(
            train_step, params, opt_state, next_batch, store, loop_cfg)
        print(f"[train] done: steps {report.start_step}->{report.end_step} "
              f"loss={report.last_metrics.get('loss'):.4f} "
              f"stragglers={len(report.stragglers)}")
        if selector is not None:
            print(f"[train] coreset: {selector.n_selected}/{selector.n_seen}"
                  f" examples selected (rate {selector.accept_rate:.4f})")


if __name__ == "__main__":
    main()
