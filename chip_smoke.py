#!/usr/bin/env python3
"""Pruned-FFN serving of llama3.2-1b on a TPU, end to end, in one process.

    python3 chip_smoke.py                 # one chip: rowsplit, then merge
    python3 chip_smoke.py --four-chips    # row-sharded plans on four chips

The one-chip run builds llama3.2-1b at its published widths with random
weights from ``--seed``, magnitude-prunes every MLP matrix to 25% through
``repro.launch.serve.prune_ffn_blocks`` and then, once per SpMM method
(``auto``, which the paper's heuristic resolves to rowsplit at these
widths, and ``merge``):

* compiles the pruned forward and checks that it holds one compiled
  Pallas kernel (``tpu_custom_call``) per SpMM launch;
* checks every pruned matrix against a plain reference, a dense matmul of
  its masked weight, on one float32 input;
* scores one batch through ``serve_pruned`` and compares its logits with
  the same model run with every pruned matrix as that dense reference, in
  the same dtypes;
* answers ragged requests through ``repro.serving.Server`` over a ladder
  of four bucket programs; every request must be answered.

``--four-chips`` runs only the sharded path: the same pruned forward with
nnz-balanced row-sharded plans over a 4-device mesh, compared with the
unsharded forward in the same process, and a check that every device
holds its own shard of the plans.

Timings, compile seconds and peak device memory go to earlier lines; the
last line is one JSON object naming the device.  Exits non-zero, with no
such line, when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "llama3.2-1b"
KEEP = 0.25
# One 128-token sequence takes the batched path (tokens ride the kernels'
# lanes per sequence); the online buckets take the flattened one (a batch
# of short sequences packed into one 128-lane tile).  Either way a call
# is one 128-column pass over every pruned matrix.
BATCH, PROMPT_LEN = 1, 128           # the scored batch
LADDER = dict(lengths=(32, 64), batches=(1, 2))    # four bucket programs
N_REQUESTS = 8
REQUEST_LENS = (8, 64)
# Each pruned matrix must match its dense reference on a float32 input to
# this fraction of the largest reference output: both sides multiply and
# accumulate in float32, in different orders.
MATRIX_TOL = 1e-4
# The logits must match the reference forward to this relative RMS error.
# Between layers the model keeps bfloat16 activations, and the two
# programs round some of them differently, which compounds over the depth:
# on the CPU, 16 layers of width 256 already differ by 1.8% RMS.  A kernel
# that drops or misplaces nonzeros errs by the logits' own scale.
LOGITS_TOL = 5e-2
MESH_SIZE = 4


class Failure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise Failure(msg)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the row-sharded forward on a 4-device "
                    "mesh against the unsharded one")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def tpu_devices():
    """The TPU devices, or Failure: no CPU fallback."""
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"JAX found no TPU (platform {devs[0].platform!r})")
    return devs


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


# -------------------------------------------------------------- reference ---


def dense_of(csr):
    """The dense (m, k) masked weight of a pruned CSR.  Pruning keeps every
    nonzero it stores (no padding) in row-major order with columns sorted
    within a row, so the flat positions are distinct and ascending, which
    lets a TPU scatter them in parallel."""
    import jax.numpy as jnp

    from repro.core.csr import rows_from_row_ptr
    m, k = csr.shape
    flat = rows_from_row_ptr(csr.row_ptr, csr.nnz_pad) * k + csr.col_ind
    return jnp.zeros(m * k, csr.vals.dtype).at[flat].set(
        csr.vals, indices_are_sorted=True, unique_indices=True).reshape(m, k)


def masked_dense_blocks(blocks):
    """``blocks`` with each pruned matrix as a dense matmul of its masked
    weight — the plain reference for ``SparseLinear``."""
    import jax
    import jax.numpy as jnp

    @jax.tree_util.register_pytree_node_class
    class MaskedDense:
        def __init__(self, weight):
            self.weight = weight            # the layer's CSR (d_out, d_in)

        def __call__(self, x, exec=None):
            # SparseLinear's dtypes: float32 operands and accumulation,
            # the result cast back to the activations' dtype.
            w = dense_of(self.weight)
            y = jnp.einsum("...i,oi->...o", x.astype(w.dtype), w,
                           precision=jax.lax.Precision.HIGHEST)
            return y.astype(x.dtype)

        def tree_flatten(self):
            return (self.weight,), None

        @classmethod
        def tree_unflatten(cls, _, children):
            return cls(*children)

    out = []
    for lp in blocks:
        for sl in lp["mlp"].values():
            check(int(sl.weight.row_ptr[-1]) == sl.weight.nnz_pad,
                  "pruned CSR carries padding; dense_of needs none")
        lp = dict(lp)
        lp["mlp"] = {k: MaskedDense(sl.weight) for k, sl in lp["mlp"].items()}
        out.append(lp)
    return out


def compare(name, got, want) -> None:
    """Logits against the reference: relative RMS within ``LOGITS_TOL``."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != "
          f"reference {want.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite logits")
    diff = got - want
    rms = float(np.sqrt(np.mean(diff ** 2) / np.mean(want ** 2)))
    log(f"{name}: max |logits - reference| = {float(np.abs(diff).max())!r}"
        f" (largest |reference| {float(np.abs(want).max())!r}); relative "
        f"RMS {rms!r}, tolerance {LOGITS_TOL}")
    check(rms <= LOGITS_TOL, f"{name}: logits differ from the reference "
          f"by {rms!r} RMS (tolerance {LOGITS_TOL})")


def check_matrices(method, blocks, seed) -> None:
    """Every pruned matrix against its dense reference, in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sparse = jax.jit(lambda sl, x: sl(x))
    dense = jax.jit(lambda md, x: md(x))
    ref_blocks = masked_dense_blocks(blocks)
    worst = 0.0
    for i, (lp, rp) in enumerate(zip(blocks, ref_blocks)):
        for name, sl in lp["mlp"].items():
            x = jax.random.normal(jax.random.PRNGKey(seed + i),
                                  (BATCH, PROMPT_LEN, sl.weight.k))
            got = np.asarray(sparse(sl, x))
            want = np.asarray(dense(rp["mlp"][name], x))
            rel = float(np.abs(got - want).max() / np.abs(want).max())
            worst = max(worst, rel)
            check(rel <= MATRIX_TOL, f"{method}: layer {i} {name}: "
                  f"{rel!r} of the reference's scale off (tolerance "
                  f"{MATRIX_TOL})")
    log(f"{method}: {sum(len(lp['mlp']) for lp in blocks)} pruned matrices "
        f"match their dense reference; worst max error {worst!r} of the "
        f"output scale (tolerance {MATRIX_TOL})")
    for name in ("w1", "w2"):
        sl = blocks[0]["mlp"][name]
        x = jnp.ones((BATCH, PROMPT_LEN, sl.weight.k))
        for label, fn, arg in (("SpMM", sparse, sl),
                               ("dense reference", dense,
                                ref_blocks[0]["mlp"][name])):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arg, x))
            log(f"{method}: {name} {sl.weight.shape} {label}, warm: "
                f"{time.perf_counter() - t0!r} s")


# --------------------------------------------------------------- one chip ---


def expected_launches(blocks) -> int:
    """Pallas launches per forward: one per pruned matrix (rowgroup:
    one per row-length group)."""
    n = 0
    for lp in blocks:
        for sl in lp["mlp"].values():
            meta = sl.plan.meta
            n += len(meta.extra) if meta.method == "rowgroup" else 1
    return n


def run_method(method, cfg, params, head, prompt, want, args, dev):
    """Prune, compile, score and serve through one SpMM method."""
    import jax
    import numpy as np

    from repro import engine, serving
    from repro.core import PlanPolicy
    from repro.launch import serve
    from repro.serving import loadgen

    policy = PlanPolicy(method=method, with_transpose=False)
    t0 = time.perf_counter()
    blocks = serve.prune_ffn_blocks(params, cfg, KEEP, policy=policy)
    jax.block_until_ready(blocks)
    log(f"{method}: plan build (prune + plans) {time.perf_counter() - t0!r}"
        f" s; methods {sorted({sl.method for lp in blocks for sl in lp['mlp'].values()})}")

    fwd = jax.jit(serve.make_pruned_forward(cfg))
    t0 = time.perf_counter()
    compiled = fwd.lower(head, blocks, prompt).compile()
    log(f"{method}: compile pruned forward {time.perf_counter() - t0!r} s")
    hlo = compiled.as_text()
    n_kernels = hlo.count('custom_call_target="tpu_custom_call"')
    want_kernels = expected_launches(blocks)
    log(f"{method}: {n_kernels} tpu_custom_call in the compiled forward "
        f"(expected {want_kernels})")
    check(n_kernels == want_kernels,
          f"{method}: {n_kernels} compiled kernels, expected {want_kernels}")

    t0 = time.perf_counter()
    check_matrices(method, blocks, args.seed)
    log(f"{method}: matrix checks {time.perf_counter() - t0!r} s")
    logits = serve.serve_pruned(cfg, head, prompt, KEEP, policy=policy,
                                blocks=blocks)
    if want is None:
        ref_fwd = jax.jit(serve.make_pruned_forward(cfg))
        t0 = time.perf_counter()
        want = np.asarray(ref_fwd(head, masked_dense_blocks(blocks),
                                  prompt))
        log(f"reference forward (cold) {time.perf_counter() - t0!r} s")
    compare(f"{method}: serve_pruned", logits, want)
    del logits

    def forward(state, tokens):
        h, blk = state
        return serve.make_pruned_forward(cfg)(h, blk, tokens)

    ladder = serving.BucketLadder(**LADDER)
    server = serving.Server(forward, (head, blocks), ladder,
                            name=f"chip_smoke.{method}")
    t0 = time.perf_counter()
    server.warmup()
    log(f"{method}: compile {len(ladder.shapes())} bucket programs "
        f"{time.perf_counter() - t0!r} s")
    solo = server.probe(ladder.max_batch, ladder.max_len)
    log(f"{method}: warm latency, bucket (batch {ladder.max_batch}, "
        f"length {ladder.max_len}): {solo!r} s")
    sched = loadgen.poisson_schedule(N_REQUESTS, 4.0 / solo, REQUEST_LENS,
                                     seed=args.seed)
    server.start()
    try:
        report = loadgen.run_load(server, sched, vocab=cfg.vocab_size,
                                  seed=args.seed)
    finally:
        server.stop()
    log(f"{method}: online {report.ok}/{report.n} ok, {report.shed} shed, "
        f"{report.error} error; p50 {report.p50_us!r} us, p99 "
        f"{report.p99_us!r} us; recompiles after warmup "
        f"{server.recompiles()}")
    check(report.ok == report.n == N_REQUESTS and report.error == 0
          and report.shed == 0,
          f"{method}: online serving answered {report.ok} of "
          f"{report.n} requests ({report.error} error, {report.shed} shed)")
    check(server.recompiles() == 0,
          f"{method}: {server.recompiles()} recompiles after warmup")
    log(f"{method}: peak_bytes_in_use {peak_bytes(dev)}")
    del server, blocks, compiled
    engine.clear_cache()
    gc.collect()
    return want


def one_chip(args, devs):
    import jax

    from repro.configs import get_config
    from repro.models import model as M

    cfg = get_config(ARCH)
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key)
    head = {k: v for k, v in params.items() if k != "segments"}
    prompt = jax.random.randint(jax.random.fold_in(key, 1),
                                (BATCH, PROMPT_LEN), 0, cfg.vocab_size)
    want = None
    for method in ("auto", "merge"):
        want = run_method(method, cfg, params, head, prompt, want, args,
                          devs[0])


# ------------------------------------------------------------- four chips ---


def check_placement(blocks, mesh) -> int:
    """Every stacked plan leaf of every sharded matrix holds shard ``i`` on
    mesh device ``i`` (compared by value in the first block).  Returns the
    leaves checked."""
    import jax
    import numpy as np
    devices = list(mesh.devices.flat)
    n = 0
    for bi, lp in enumerate(blocks):
        for name, sl in lp["mlp"].items():
            plan = sl.plan
            check(plan.meta.spmd_mesh() is not None,
                  f"{name}: sharded plan runs the per-shard loop, not one "
                  "program over the mesh")
            stacked, _, _ = plan._stacked()
            per_shard = [jax.tree.leaves(p) for p in plan.shards]
            for li, leaf in enumerate(jax.tree.leaves(stacked)):
                shards = leaf.addressable_shards
                check(len(shards) == len(devices),
                      f"{name}: {len(shards)} shards of a plan leaf for "
                      f"{len(devices)} devices")
                for s in shards:
                    i = s.index[0].start or 0
                    check(s.device == devices[i],
                          f"{name}: shard {i} on {s.device}, expected "
                          f"{devices[i]}")
                    check(bi > 0 or np.array_equal(
                        np.asarray(s.data)[0], np.asarray(per_shard[i][li])),
                        f"{name}: device {s.device} holds the wrong slice "
                        "of a plan leaf")
                n += 1
    return n


def replicate(tree, mesh):
    """Every leaf not yet placed, copied to every mesh device once: a
    data-parallel replica's weights.  The sharded plans' stacked leaves
    are already placed, one shard per device, and stay as they are."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    everywhere = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: x if x.committed else jax.device_put(x, everywhere), tree)


def four_chips(args, devs):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.core import PlanPolicy, ShardSpec
    from repro.launch import serve
    from repro.models import model as M

    check(len(devs) >= MESH_SIZE,
          f"--four-chips needs {MESH_SIZE} devices, JAX found {len(devs)}")
    cfg = get_config(ARCH)
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key)
    head = {k: v for k, v in params.items() if k != "segments"}
    prompt = jax.random.randint(jax.random.fold_in(key, 1),
                                (BATCH, PROMPT_LEN), 0, cfg.vocab_size)
    fwd = jax.jit(serve.make_pruned_forward(cfg))

    base = PlanPolicy(with_transpose=False)
    t0 = time.perf_counter()
    blocks = serve.prune_ffn_blocks(params, cfg, KEEP, policy=base)
    jax.block_until_ready(blocks)
    log(f"unsharded plan build {time.perf_counter() - t0!r} s")
    t0 = time.perf_counter()
    want = np.asarray(fwd(head, blocks, prompt))
    log(f"unsharded forward on {devs[0]} (cold) "
        f"{time.perf_counter() - t0!r} s")
    del blocks
    gc.collect()

    mesh = Mesh(np.array(devs[:MESH_SIZE]), ("data",))
    sharded = dataclasses.replace(base, shards=ShardSpec(mesh=mesh))
    t0 = time.perf_counter()
    blocks = serve.prune_ffn_blocks(params, cfg, KEEP, policy=sharded)
    jax.block_until_ready(blocks)
    log(f"sharded plan build ({MESH_SIZE} row shards per matrix) "
        f"{time.perf_counter() - t0!r} s")
    n_leaves = check_placement(blocks, mesh)
    log(f"placement: {n_leaves} stacked plan leaves, each with shard i on "
        f"mesh device i")
    head, blocks, prompt = replicate((head, blocks, prompt), mesh)
    t0 = time.perf_counter()
    compiled = fwd.lower(head, blocks, prompt).compile()
    log(f"compile sharded forward {time.perf_counter() - t0!r} s")
    n_kernels = compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    want_kernels = expected_launches_sharded(blocks)
    log(f"{n_kernels} tpu_custom_call in the compiled sharded forward "
        f"(expected {want_kernels})")
    check(n_kernels == want_kernels, f"{n_kernels} compiled kernels, "
          f"expected {want_kernels}")
    t0 = time.perf_counter()
    got = jax.block_until_ready(compiled(head, blocks, prompt))
    log(f"sharded forward (first call) {time.perf_counter() - t0!r} s")
    t0 = time.perf_counter()
    got = jax.block_until_ready(compiled(head, blocks, prompt))
    log(f"sharded forward (warm) {time.perf_counter() - t0!r} s")
    compare(f"sharded over {MESH_SIZE} devices vs unsharded", got, want)
    for d in devs[:MESH_SIZE]:
        log(f"{d}: peak_bytes_in_use {peak_bytes(d)}")


def expected_launches_sharded(blocks) -> int:
    """One SPMD program per sharded matrix: its local kernel appears once
    in the partitioned program (each device runs it on its own shard)."""
    return sum(1 for lp in blocks for _ in lp["mlp"].values())


# ------------------------------------------------------------------ main ---


def main(argv=None) -> int:
    args = parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"[chip_smoke] FAIL: no repro package under {src}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, src)
    from repro.launch.cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    try:
        devs = tpu_devices()
        dev = devs[0]
        log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")
        t0 = time.perf_counter()
        if args.four_chips:
            four_chips(args, devs)
            count = MESH_SIZE
        else:
            one_chip(args, devs)
            count = len(devs)
        log(f"total {time.perf_counter() - t0!r} s")
    except Failure as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
