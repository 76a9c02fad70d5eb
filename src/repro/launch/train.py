"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --reduced \
      --steps 200 --batch 8 --seq 128 --workdir /tmp/run1

On a real cluster this process is started once per host (jax.distributed
initialises from the TPU/GKE environment); on this container it drives the
same Trainer on CPU with the reduced configs.  Elastic restart: rerunning
with the same --workdir resumes from the latest checkpoint on whatever
device count is available (mesh-agnostic checkpoints).
"""
from __future__ import annotations

import argparse
import os

import jax

from repro.configs import ARCH_NAMES, get_config
from repro.launch.mesh import make_host_mesh
from repro.train.anomaly import AnomalyConfig
from repro.train.data import BinaryShardData, SyntheticLMData
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import Trainer
from repro.utils.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale)")
    ap.add_argument("--impl", default=None, help="attention impl override")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--data", default=None,
                    help="glob of .bin token shards (default: synthetic)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--context-parallel", type=int, default=1,
                    help="ring sequence-parallel attention degree: shards "
                         "the sequence axis over a 'context' mesh axis "
                         "(distributed.ring_attention), so max trainable "
                         "sequence length scales with this instead of HBM "
                         "per chip")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-every", type=int, default=200)
    ap.add_argument("--anomaly-z", type=float, default=8.0,
                    help="z-score threshold of the loss/grad-norm spike "
                         "detector (rolls back to the last verified "
                         "checkpoint; 0 disables the guard)")
    ap.add_argument("--max-rollbacks", type=int, default=3,
                    help="consecutive no-progress anomaly rollbacks before "
                         "the run halts (AnomalyHalt)")
    ap.add_argument("--supervise", type=int, default=0, metavar="N",
                    help="run under TrainSupervisor with N simulated "
                         "workers: heartbeat failure detection, straggler "
                         "exclusion, remesh + verified-checkpoint restore "
                         "on worker loss (0 = plain Trainer)")
    ap.add_argument("--tune", choices=["off", "analytic", "measure"],
                    default=None,
                    help="block-size autotuning mode (sets REPRO_TUNE; "
                         "default: inherit the environment)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace_event JSON of the run "
                         "(per-step data/fwd_bwd spans, checkpoint and "
                         "rollback instants; Perfetto-loadable)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a typed metrics snapshot of the run")
    args = ap.parse_args()
    print(f"[train] compile cache: {use_compile_cache()}")

    if args.tune:
        os.environ["REPRO_TUNE"] = args.tune

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.impl:
        cfg = cfg.replace(attention=cfg.attention.with_impl(args.impl))
    if args.context_parallel > 1:
        from dataclasses import replace as dc_replace

        cfg = cfg.replace(
            attention=dc_replace(cfg.attention, context_axis="context")
        )

    mesh = None
    if len(jax.devices()) > 1 or args.context_parallel > 1:
        mesh = make_host_mesh(args.model_parallel, args.context_parallel)
        print(f"[train] mesh: {dict(mesh.shape)}")

    # Resolve (and under measure mode, sweep + persist) the training-shape
    # attention blocks up front, so the first jitted step never hides a
    # timing run.  Explicit config ints pass through untouched.  Under the
    # mesh context the tuner keys go per-shard when context parallelism is
    # on (the ring streams one shard per device, not the global sequence).
    from repro.distributed.sharding import maybe_set_mesh

    acfg = cfg.attention
    if acfg.impl != "reference" and (acfg.block_q is None or acfg.block_k is None):
        from repro.core.api import resolve_attention_blocks

        with maybe_set_mesh(mesh):
            blocks = resolve_attention_blocks(
                acfg, d=cfg.head_dim_, n_q=args.seq,
                dtype="bfloat16" if cfg.compute_dtype == "bfloat16" else "float32",
                causal=True, bwd=True,  # training traces the backward kernels
            )
        print(f"[train] attention blocks ({os.environ.get('REPRO_TUNE', 'off')}): "
              f"{blocks}")

    opt_cfg = OptimizerConfig(
        peak_lr=args.lr,
        warmup_steps=max(args.steps // 20, 1),
        total_steps=args.steps,
        schedule=cfg.schedule,
        grad_accum=args.grad_accum,
    )
    if args.data:
        import glob

        data = BinaryShardData(sorted(glob.glob(args.data)), args.batch, args.seq)
    else:
        data = SyntheticLMData(cfg.vocab, args.batch, args.seq, seed=args.seed)

    os.makedirs(args.workdir, exist_ok=True)
    anomaly = AnomalyConfig(
        enabled=args.anomaly_z > 0,
        z_threshold=args.anomaly_z or 8.0,
        max_rollbacks=args.max_rollbacks,
    )
    rec = None
    if args.trace:
        from repro.obs import TraceRecorder, set_recorder

        rec = TraceRecorder()
        set_recorder(rec)  # autotune measurement spans ride the global

    trainer = Trainer(cfg, opt_cfg, data, workdir=args.workdir, mesh=mesh,
                      seed=args.seed, ckpt_every=args.ckpt_every,
                      anomaly=anomaly, trace=rec)
    source = trainer
    if args.supervise > 0:
        from repro.train.supervisor import TrainSupervisor

        sup = TrainSupervisor(trainer, num_workers=args.supervise,
                              model_parallel=args.model_parallel, trace=rec)
        hist = sup.run(args.steps)
        print(f"[train] supervisor counters: {sup.counters_snapshot()}")
        source = sup
    else:
        hist = trainer.run(args.steps)
    if hist:
        print(f"[train] done: loss {hist[0]['loss']:.4f} → {hist[-1]['loss']:.4f} "
              f"over {len(hist)} steps")
    if rec is not None:
        rec.save(args.trace)
        print(f"[train] trace: {args.trace} ({len(rec.events)} events)")
    if args.metrics_out:
        import json

        from repro.obs import train_registry

        with open(args.metrics_out, "w") as f:
            json.dump(train_registry(source).snapshot(), f, indent=1)
        print(f"[train] metrics: {args.metrics_out}")


if __name__ == "__main__":
    main()
