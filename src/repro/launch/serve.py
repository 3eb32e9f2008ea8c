"""Serving driver: prefill + batched greedy decode on the local mesh,
with optional pruned-FFN SpMM (the paper's use case).

    python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --batch 4 --prompt-len 32 --gen 16

    # pruned-FFN scoring through the plan-once/execute-many SpMM engine
    python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --batch 2 --prompt-len 16 --prune-ffn 0.25
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.launch.cache import enable_compile_cache
from repro.models import layers as L
from repro.models import model as M
from repro.models import sparse as S
from repro.runtime import steps as R

# Per-phase serving latency: "plan" (prune + plan build), "cold" (first
# jitted forward, compile included), "warm" (steady state).
_serve_latency = obs.registry.histogram(
    "serve_latency_us", "serve.py phase latency", labels=("phase",))
_serve_replans = obs.registry.counter(
    "serve_replans_total",
    "plans built inside the jitted serving path (must stay 0)")


def _check_replans(before, after) -> int:
    """Count plan-cache misses between two ``engine.cache_stats()``
    snapshots and fail loudly if the jitted serving path built any.

    A real check, not an ``assert`` — ``python -O`` strips asserts, and
    a replanning hot path is exactly the regression serving must never
    ship with.  The count lands on ``serve_replans_total`` either way so
    dashboards see the violation even if the exception is swallowed.
    """
    replans = after.misses - before.misses
    if replans:
        _serve_replans.inc(replans)
        raise RuntimeError(
            f"jitted serving replanned: {replans} plan(s) built during "
            f"the warm forward (cache misses {before.misses} -> "
            f"{after.misses}). Plans must be attached before jit — "
            "rebuild the sparse params with ensure_spmm_plans/prune_mlp "
            "outside the traced function.")
    return replans


def generate(cfg, params, prompt_tokens, gen_len: int, *, cache_extra=8):
    """Greedy decode. prompt_tokens (b, s) → (b, s+gen_len)."""
    b, s = prompt_tokens.shape
    prefill = jax.jit(R.make_prefill_step(cfg, cache_len=s + gen_len
                                          + cache_extra))
    decode = jax.jit(R.make_decode_step(cfg))
    out = prefill(params, {"tokens": prompt_tokens})
    caches, logits, pos = out["caches"], out["logits"], out["pos"]
    toks = [prompt_tokens]
    cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(gen_len):
        toks.append(cur)
        logits, caches = decode(params, caches, {"tokens": cur}, pos)
        cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        pos = pos + 1
    return jnp.concatenate(toks, axis=1)


_PRUNABLE_BTYPES = ("attn", "rglru")   # blocks that own a dense "mlp"


def check_prunable(cfg):
    btypes = {bt for pattern, _ in cfg.segments for bt in pattern}
    unsupported = btypes - set(_PRUNABLE_BTYPES)
    if unsupported:
        raise SystemExit(
            f"--prune-ffn needs every block to own a dense MLP "
            f"(btypes {_PRUNABLE_BTYPES}); arch has {sorted(unsupported)} "
            "blocks (MoE experts / SSD cores have no per-block dense FFN "
            "to prune)")


def prune_ffn_blocks(params, cfg, keep: float, policy=None):
    """Unstack each block's params and prune its MLP once, building each
    pattern's plan through the engine cache — plans are reused by every
    subsequent jitted call.  ``policy`` (a ``repro.PlanPolicy``) pins the
    plan request, e.g. a forced kernel method from ``--spmm-method``."""
    blocks = []
    for si, (pattern, count) in enumerate(cfg.segments):
        for ci in range(count):
            for pi, btype in enumerate(pattern):
                lp = jax.tree.map(lambda x: x[ci],
                                  params["segments"][si][pi])
                lp["mlp"] = S.prune_mlp(lp["mlp"], keep, policy=policy)
                blocks.append(lp)
    return blocks


def block_types(cfg):
    return [btype for pattern, count in cfg.segments
            for _ in range(count) for btype in pattern]


def make_pruned_forward(cfg):
    """Unstacked full forward with SparseLinear MLPs (jit-ready).

    Routes through ``model.block_apply`` — parallel blocks, attention
    windows, and norms behave exactly as in the dense model; only
    ``mlp_apply`` dispatches to the sparse layers.  The SparseLinear
    leaves carry their SpmmPlans, so the jitted trace executes prebuilt
    plans — no replanning, no host syncs.
    """
    btypes = block_types(cfg)      # static: jit sees only the param pytree

    def fwd(params, blocks, tokens):
        h = M.embed_inputs(params, cfg, {"tokens": tokens})
        for btype, lp in zip(btypes, blocks):
            h, _, _ = M.block_apply(lp, btype, h, cfg)
        with obs.scope("norm"):
            h = L.norm_apply(params["final_norm"], h, cfg.norm,
                             cfg.norm_eps)
        with obs.scope("unembed"):
            return M.final_logits(h.astype(jnp.float32) @ M.unembed_matrix(
                params, cfg).T.astype(jnp.float32), cfg)

    return fwd


def dense_shardings(tree, mesh):
    """Where each dense weight of a pruned model lies on the one-axis
    serving mesh: the embedding by vocabulary rows, ``wq``/``wk``/``wv``
    by output heads and ``wo`` by input heads over the mesh's axis, every
    other leaf (norms) whole on each device.  The rules are
    ``sharding.param_pspec``'s tensor-parallel ones (mode ``"tp"``), their
    ``model`` axis read as the mesh's one axis.  ``tree`` may hold arrays
    or ``ShapeDtypeStruct``s."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import param_pspec
    (axis,) = mesh.axis_names
    tp = Mesh(mesh.devices.reshape(1, -1), ("data", "model"))

    def one(path, leaf):
        spec = param_pspec(path, leaf, tp, mode="tp")
        return NamedSharding(mesh, P(*[axis if a == "model" else a
                                       for a in spec]))
    return jax.tree_util.tree_map_with_path(one, tree)


def place_on_mesh(head, blocks, mesh):
    """A pruned model's weights placed on the serving mesh its sharded
    SpMM plans run over: the dense weights as ``dense_shardings`` says,
    each pruned layer's global CSR whole on every device (the shard_map
    gathers each shard's values from it every call), its plan's shards
    where the plan put them.  Returns ``(head, blocks)``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    everywhere = NamedSharding(mesh, P())
    head = jax.device_put(head, dense_shardings(head, mesh))
    placed = []
    for lp in blocks:
        dense = {k: v for k, v in lp.items() if k != "mlp"}
        out = dict(jax.device_put(dense, dense_shardings(dense, mesh)))
        out["mlp"] = {name: dataclasses.replace(
            sl, weight=jax.device_put(sl.weight, everywhere))
            for name, sl in lp["mlp"].items()}
        placed.append(out)
    return head, placed


def _placed(params, blocks, policy):
    """With a sharded plan policy over a mesh, the weights the pruned
    forward reads placed on that mesh (``place_on_mesh``); else as
    given."""
    mesh = policy.shards.mesh if policy is not None and \
        policy.shards is not None else None
    if mesh is None:
        return params, blocks
    head = {k: v for k, v in params.items() if k != "segments"}
    return place_on_mesh(head, blocks, mesh)


def serve_pruned(cfg, params, prompt, keep: float, *, microbatch: int = 0,
                 policy=None, blocks=None):
    """Score ``prompt`` through the pruned-FFN forward, cold then warm.

    ``blocks`` are ``prune_ffn_blocks``' output when the caller already
    pruned (then ``keep``/``policy`` only label the report)."""
    from repro import engine

    check_prunable(cfg)
    t0 = time.perf_counter()
    if blocks is None:
        with obs.span("serve.plan", cat="serve", keep=keep):
            blocks = prune_ffn_blocks(params, cfg, keep, policy=policy)
    params, blocks = _placed(params, blocks, policy)
    t_plan = time.perf_counter() - t0
    _serve_latency.labels(phase="plan").observe(t_plan * 1e6)
    stats = engine.cache_stats()
    methods = {k: v.method for k, v in blocks[0]["mlp"].items()}
    print(f"[serve] pruned {len(blocks)} MLPs (keep={keep:.0%}) "
          f"in {t_plan:.2f}s; methods={methods}; "
          f"plan cache: {stats.misses} built, {stats.hits} reused")

    fwd = jax.jit(make_pruned_forward(cfg))
    if microbatch:
        # One compiled microbatch program serves the whole request batch:
        # compile cost is paid for the microbatch shape only, and each
        # slice's batch axis rides the engine's batched plan execution.
        fwd = R.microbatched(fwd, microbatch, argnums=(2,))
    t_cold0 = time.perf_counter()
    with obs.span("serve.forward_cold", cat="serve"):
        logits = jax.block_until_ready(fwd(params, blocks, prompt))
    _serve_latency.labels(phase="cold").observe(
        (time.perf_counter() - t_cold0) * 1e6)
    t1 = time.perf_counter()
    with obs.span("serve.forward_warm", cat="serve"):
        logits = jax.block_until_ready(fwd(params, blocks, prompt))
    t_warm = time.perf_counter() - t1
    _serve_latency.labels(phase="warm").observe(t_warm * 1e6)
    after = engine.cache_stats()
    replans = _check_replans(stats, after)
    mb = f" (microbatch={microbatch})" if microbatch else ""
    print(f"[serve] warm pruned forward{mb} {t_warm * 1e3:.1f}ms "
          f"({prompt.size / t_warm:.0f} tok/s); plans built during "
          f"serving: {replans}")
    return logits


def serve_online(cfg, params, keep: float, args, policy=None) -> int:
    """``--serve``: online continuous batching over the pruned-FFN
    forward.  Ragged Poisson arrivals pack into pre-compiled
    ``(batch, length)`` bucket programs (``repro.serving``); after
    warmup the run must neither replan nor recompile — both asserted.
    """
    from repro import engine, serving
    from repro.serving import loadgen

    check_prunable(cfg)
    with obs.span("serve.plan", cat="serve", keep=keep):
        blocks = prune_ffn_blocks(params, cfg, keep, policy=policy)
    params, blocks = _placed(params, blocks, policy)
    base = make_pruned_forward(cfg)

    def forward(state, tokens):
        p, blk = state
        return base(p, blk, tokens)

    ladder = serving.BucketLadder.from_max(
        args.prompt_len, max(args.batch, 1),
        min_len=min(8, args.prompt_len))
    server = serving.Server(
        forward, (params, blocks), ladder,
        queue_depth=args.serve_queue_depth,
        default_deadline_s=(args.serve_deadline_ms / 1e3
                            if args.serve_deadline_ms else None),
        name="serve.online")
    t0 = time.perf_counter()
    server.warmup()
    shapes = ladder.shapes()
    print(f"[serve] warmed {len(shapes)} bucket programs "
          f"(lengths={ladder.lengths} batches={ladder.batches}) "
          f"in {time.perf_counter() - t0:.2f}s")
    plan_stats = engine.cache_stats()

    rate = args.serve_rate
    if rate <= 0:
        # Auto-rate: drive at ~4x the solo warm-call capacity so the
        # batcher actually batches.
        solo = min(server.probe(ladder.batches[0], ladder.max_len)
                   for _ in range(3))
        rate = 4.0 / solo
        print(f"[serve] auto rate: solo call {solo * 1e3:.1f}ms "
              f"-> offered {rate:.1f} req/s")
    sched = loadgen.poisson_schedule(
        args.serve_requests, rate,
        (max(1, args.prompt_len // 4), args.prompt_len), seed=args.seed)
    server.start()
    report = loadgen.run_load(server, sched, vocab=cfg.vocab_size,
                              seed=args.seed)
    server.stop()
    _check_replans(plan_stats, engine.cache_stats())
    rc = server.recompiles()
    if rc:
        raise RuntimeError(
            f"online serving recompiled {rc} program(s) after warmup — "
            "the bucket ladder must cover every served shape")
    print(f"[serve] online: {report.ok}/{report.n} ok "
          f"({report.shed} shed, {report.error} error) in "
          f"{report.wall_s:.2f}s = {report.throughput_rps:.1f} req/s; "
          f"p50 {report.p50_us / 1e3:.1f}ms p99 "
          f"{report.p99_us / 1e3:.1f}ms; recompiles after warmup: {rc}")
    _export_obs(args)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prune-ffn", type=float, default=0.0, metavar="KEEP",
                    help="serve with magnitude-pruned FFNs (CSR SpMM via "
                    "the plan engine); KEEP is the kept fraction per row")
    ap.add_argument("--microbatch", type=int, default=0, metavar="MB",
                    help="score pruned-FFN requests in fixed-size "
                    "microbatches (must divide --batch): one compiled "
                    "program per microbatch shape, batch axis folded into "
                    "the SpMM kernel grid")
    ap.add_argument("--tunedb", default="", metavar="PATH",
                    help="TuneDB JSON (python -m repro.tune) — pruned-FFN "
                    "plans resolve their method from measurements "
                    "instead of the paper's fixed threshold")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="shard every pruned-FFN weight over a N-device "
                    "data mesh: nnz-balanced row shards, one local plan "
                    "per shard, executed as a single shard_map program; "
                    "the embedding by vocabulary rows and attention by "
                    "heads over the same devices "
                    "(CPU dev boxes: XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="enable structured tracing and write the Chrome "
                    "trace-event JSON (Perfetto-viewable) here on exit "
                    "(REPRO_TRACE=1 enables tracing without a file)")
    ap.add_argument("--metrics-out", default="", metavar="PATH",
                    help="write a JSON snapshot of the metrics registry "
                    "(latency histograms, plan-cache counters, ladder "
                    "rung rates) here on exit")
    from repro.kernels import registry
    ap.add_argument("--spmm-method", default="auto",
                    choices=("auto",) + registry.method_names(),
                    help="force the SpMM kernel method for pruned-FFN "
                    "plans (any registered method; 'auto' resolves "
                    "through the TuneDB ladder + heuristic)")
    ap.add_argument("--serve", action="store_true",
                    help="online mode: continuous batching of ragged "
                    "Poisson requests over pre-compiled shape-bucket "
                    "programs (requires --prune-ffn); --batch and "
                    "--prompt-len bound the bucket ladder")
    ap.add_argument("--serve-requests", type=int, default=24,
                    metavar="N", help="requests in the Poisson load")
    ap.add_argument("--serve-rate", type=float, default=0.0,
                    metavar="RPS", help="offered load (0 = auto: 4x the "
                    "measured solo-call capacity)")
    ap.add_argument("--serve-deadline-ms", type=float, default=0.0,
                    metavar="MS", help="per-request deadline; expired "
                    "requests are shed, not served (0 = none)")
    ap.add_argument("--serve-queue-depth", type=int, default=64,
                    metavar="N", help="admission queue bound; submits "
                    "beyond it are shed immediately")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.prune_ffn <= 0.0:
        # These flags only shape the pruned-FFN path; silently ignoring
        # them hides typos like a forgotten --prune-ffn.
        dead = [fl for fl, on in (
            ("--serve", args.serve),
            ("--microbatch", args.microbatch != 0),
            ("--mesh", args.mesh != 0),
            ("--spmm-method", args.spmm_method != "auto"),
        ) if on]
        if dead:
            ap.error(f"{', '.join(dead)}: no effect without "
                     "--prune-ffn KEEP (the dense decode path ignores "
                     "these flags); add --prune-ffn or drop them")

    if args.trace_out:
        obs.enable()

    if args.tunedb:
        from repro import engine
        db = engine.load_tunedb(args.tunedb)
        print(f"[serve] tunedb {args.tunedb}: backend={db.backend} "
              f"entries={len(db)} threshold={db.threshold}")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    assert cfg.input_mode == "tokens", \
        "serve.py drives token models; embeddings-mode archs use the " \
        "prefill/decode steps directly (see examples/)"
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    if args.prune_ffn > 0.0:
        from repro.core import PlanPolicy, ShardSpec
        # Serving never differentiates: no backward (transpose) plans.
        policy = PlanPolicy(method=args.spmm_method, with_transpose=False)
        if args.mesh:
            import numpy as np
            from jax.sharding import Mesh
            ndev = len(jax.devices())
            if args.mesh > ndev:
                raise SystemExit(
                    f"--mesh {args.mesh} exceeds the {ndev} local "
                    "device(s); on CPU force more with XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={args.mesh}")
            mesh = Mesh(np.array(jax.devices()[:args.mesh]), ("data",))
            policy = dataclasses.replace(policy,
                                         shards=ShardSpec(mesh=mesh))
            print(f"[serve] sharding pruned-FFN plans over {args.mesh} "
                  f"devices (nnz-balanced row shards)")
        if args.serve:
            return serve_online(cfg, params, args.prune_ffn, args,
                                policy=policy)
        logits = serve_pruned(cfg, params, prompt, args.prune_ffn,
                              microbatch=args.microbatch, policy=policy)
        print(f"pruned-FFN logits {logits.shape}; "
              f"argmax@last {jnp.argmax(logits[:, -1], -1)}")
        _export_obs(args)
        return 0
    t0 = time.perf_counter()
    with obs.span("serve.generate", cat="serve", gen=args.gen):
        out = generate(cfg, params, prompt, args.gen)
    dt = time.perf_counter() - t0
    _serve_latency.labels(phase="generate").observe(dt * 1e6)
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print(out[0, -args.gen:])
    _export_obs(args)
    return 0


def _export_obs(args) -> None:
    if args.trace_out:
        tr = obs.get_tracer()
        if tr is not None:
            print(f"[serve] trace: {tr.export(args.trace_out)} "
                  f"({len(tr)} events)")
    if args.metrics_out:
        print(f"[serve] metrics: {obs.dump_metrics(args.metrics_out)}")


if __name__ == "__main__":
    raise SystemExit(main())
