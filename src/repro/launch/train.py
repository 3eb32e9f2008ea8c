"""Training driver: real steps on the local mesh (CPU here, TPU pod in
production), with checkpoint/resume, preemption handling, straggler
watermarking, and deterministic data.

    python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 50 --global-batch 8 --seq-len 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse

import jax

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.data import DataConfig, make_source
from repro.distributed import fault
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.optim import adamw
from repro.runtime import steps as R

# Step latency (the first observation includes compile; the histogram's
# p50 reads as steady state, max as the compile step).
_step_latency = obs.registry.histogram(
    "train_step_latency_us", "train.py per-step wall time")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8_ef"])
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--data-path", default="")
    ap.add_argument("--tunedb", default="", metavar="PATH",
                    help="TuneDB JSON (python -m repro.tune) — SparseLinear "
                    "plan (re)builds resolve their kernel method from "
                    "measurements instead of the analytic heuristic")
    ap.add_argument("--spmm-method", default="", metavar="METHOD",
                    help="force the SpMM kernel method for sparse-layer "
                    "plan rebuilds (any method registered in "
                    "repro.kernels.registry; default: auto)")
    ap.add_argument("--spmm-shards", type=int, default=0, metavar="N",
                    help="rebuild sparse-layer plans as N nnz-balanced "
                    "row shards (repro.distributed.spmm); when N matches "
                    "the local mesh's data axis the shards execute as one "
                    "shard_map program, otherwise as a per-shard loop")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="enable structured tracing and write the Chrome "
                    "trace-event JSON here on exit")
    ap.add_argument("--metrics-out", default="", metavar="PATH",
                    help="write a JSON snapshot of the metrics registry "
                    "(step-latency histogram, plan counters) on exit")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.trace_out:
        obs.enable()

    if args.tunedb:
        from repro import engine
        db = engine.load_tunedb(args.tunedb)
        print(f"[train] tunedb {args.tunedb}: backend={db.backend} "
              f"entries={len(db)} threshold={db.threshold}")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh()
    opt_cfg = adamw.AdamWConfig(learning_rate=args.lr,
                                warmup_steps=args.warmup,
                                total_steps=args.steps)
    step_fn = R.make_train_step(
        cfg, opt_cfg, microbatches=args.microbatches,
        loss_chunk=min(512, args.seq_len),
        grad_compression=args.grad_compression)

    state = R.init_train_state(cfg, jax.random.PRNGKey(args.seed),
                               grad_compression=args.grad_compression)
    start_step = 0
    manager = None
    if args.ckpt_dir:
        manager = CheckpointManager(args.ckpt_dir, keep=3)
        if args.resume == "auto":
            restored, step, extra = manager.restore_latest(state)
            if restored is not None:
                state, start_step = restored, step
                print(f"[train] resumed from step {step}")
    # Route any sparse layers/matrices through the SpMM engine: plans are
    # (re)built once here, outside jit — the jitted step never replans.
    spmm_policy = None
    if args.spmm_method or args.spmm_shards:
        from repro.core import PlanPolicy, ShardSpec
        shards = None
        if args.spmm_shards:
            shard_mesh = (mesh if mesh.shape.get("data") == args.spmm_shards
                          else None)
            shards = ShardSpec(n=args.spmm_shards, mesh=shard_mesh)
        spmm_policy = PlanPolicy(method=args.spmm_method or "auto",
                                 shards=shards)
    state["params"] = R.ensure_spmm_plans(state["params"],
                                          policy=spmm_policy)

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.global_batch, seed=args.seed,
                          input_mode=cfg.input_mode, d_model=cfg.d_model)
    source = make_source(data_cfg, args.data_path or None)

    with mesh:
        jitted = jax.jit(step_fn, donate_argnums=(0,))
        guard = fault.PreemptionGuard().install()
        watermark = fault.StragglerWatermark()
        for step in range(start_step, args.steps):
            batch = source.batch_at(step)
            with fault.StepTimer() as t:
                with obs.span("train.step", cat="train", step=step):
                    state, metrics = jitted(state, batch)
                    jax.block_until_ready(metrics["loss"])
            _step_latency.observe(t.seconds * 1e6)
            if watermark.observe(step, t.seconds):
                print(f"[straggler] step {step} took {t.seconds:.2f}s")
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                      f"nll={float(metrics['nll']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} {t.seconds:.2f}s")
            want_ckpt = manager and (
                (step + 1) % args.ckpt_every == 0 or step == args.steps - 1
                or guard.should_checkpoint())
            if want_ckpt:
                fault.retry(lambda: manager.save(step + 1, state))
            if guard.should_checkpoint():
                print(f"[train] preempted; checkpointed at {step + 1}; "
                      f"exiting for restart")
                _export_obs(args)
                return 0
    if watermark.flagged:
        print(f"[train] stragglers flagged: {watermark.flagged[:5]}")
    _export_obs(args)
    return 0


def _export_obs(args) -> None:
    if args.trace_out:
        tr = obs.get_tracer()
        if tr is not None:
            print(f"[train] trace: {tr.export(args.trace_out)} "
                  f"({len(tr)} events)")
    if args.metrics_out:
        print(f"[train] metrics: {obs.dump_metrics(args.metrics_out)}")


if __name__ == "__main__":
    raise SystemExit(main())
