"""Batched, K-tiled plan execution — and the silent-wrong-answer guards.

Tentpole acceptance (ISSUE 3): ``execute_plan`` on ``(batch, k, n)`` B and
``jax.vmap(execute_plan)`` agree with a stacked per-matrix loop to 1e-5
(f32), gradients included, through both kernel methods and both impls;
K-tiled kernels bit-match the whole-K dataflow when a single panel covers
``k``.  Satellites: undersized ``l_pad`` raises instead of truncating,
conflicting plan overrides raise instead of being ignored, degenerate
patterns (0-nnz, 0-row, 1-row) execute and differentiate.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.sparse as S
from repro.core import (CSR, ExecutionConfig, PlanPolicy, build_plan,
                        execute_plan, random_csr, spmm)
from repro.kernels import merge_spmm as M
from repro.kernels import ref
from repro.kernels.merge_spmm import resolve_tk

EC = ExecutionConfig  # keep call sites within the line limit

TOL = dict(rtol=1e-5, atol=1e-5)
METHODS = ["merge", "rowsplit"]
IMPLS = ["xla", "pallas"]
BATCH = 3


def _case(seed=0, m=40, k=32, n=16, npr=(0, 10)):
    a = random_csr(jax.random.PRNGKey(seed), m, k, nnz_per_row=npr)
    bs = jax.random.normal(jax.random.PRNGKey(seed + 1), (BATCH, k, n))
    w = jax.random.normal(jax.random.PRNGKey(seed + 2), (BATCH, m, n))
    return a, bs, w


def _loop(plan, vals, bs, impl):
    return jnp.stack([execute_plan(plan, vals, bs[i], EC(impl=impl))
                      for i in range(bs.shape[0])])


# ------------------------------------------------------- batched forward ---


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("method", METHODS)
def test_batched_matches_per_matrix_loop(method, impl):
    a, bs, _ = _case()
    plan = build_plan(a, method=method)
    got = execute_plan(plan, a.vals, bs, EC(impl=impl))
    want = _loop(plan, a.vals, bs, impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    dense = np.asarray(a.to_dense())
    np.testing.assert_allclose(
        np.asarray(got), np.stack([dense @ np.asarray(bs[i])
                                   for i in range(BATCH)]), **TOL)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("method", METHODS)
def test_vmap_matches_per_matrix_loop(method, impl):
    a, bs, _ = _case(seed=3)
    plan = build_plan(a, method=method)
    got = jax.vmap(lambda b: execute_plan(plan, a.vals, b, EC(impl=impl)))(bs)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_loop(plan, a.vals, bs, impl)),
                               **TOL)


def test_batched_under_jit_and_leading_dims():
    """Extra leading dims fold into one batch axis; jit changes nothing."""
    a, bs, _ = _case(seed=4)
    plan = build_plan(a, method="merge")
    b4 = jnp.stack([bs, 2.0 * bs])                 # (2, BATCH, k, n)
    got = jax.jit(lambda v, b: execute_plan(plan, v, b, EC(impl="pallas")))(
        a.vals, b4)
    assert got.shape == (2, BATCH, a.m, bs.shape[-1])
    np.testing.assert_allclose(np.asarray(got[1]),
                               2 * np.asarray(got[0]), **TOL)
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(_loop(plan, a.vals, bs, "pallas")),
        **TOL)


def test_stale_plan_shape_guard_batched():
    a, bs, _ = _case(seed=5)
    plan = build_plan(a, method="merge")
    with pytest.raises(ValueError, match="expects B of shape"):
        execute_plan(plan, a.vals, bs[:, :-1])     # wrong k
    with pytest.raises(ValueError, match="expects B of shape"):
        execute_plan(plan, a.vals, bs[0, :, 0])    # 1-D


# ------------------------------------------------------------- gradients ---


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("method", METHODS)
def test_batched_grad_matches_loop(method, impl):
    """Shared-values grads: batched == sum-over-stack loop, dB per element."""
    a, bs, w = _case(seed=6)
    plan = build_plan(a, method=method)

    def loss(vals, b):
        return jnp.sum(execute_plan(plan, vals, b, EC(impl=impl)) * w)

    def loss_loop(vals, b):
        return sum(
            jnp.sum(execute_plan(plan, vals, b[i], EC(impl=impl)) * w[i])
            for i in range(BATCH))

    gv, gb = jax.grad(loss, argnums=(0, 1))(a.vals, bs)
    wv, wb = jax.grad(loss_loop, argnums=(0, 1))(a.vals, bs)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv), **TOL)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(wb), **TOL)


@pytest.mark.parametrize("method", METHODS)
def test_grad_of_vmap_matches_loop(method):
    a, bs, w = _case(seed=7)
    plan = build_plan(a, method=method)

    def loss(vals, b):
        out = jax.vmap(lambda bi: execute_plan(plan, vals, bi,
                                               EC(impl="pallas")))(b)
        return jnp.sum(out * w)

    def loss_loop(vals, b):
        return sum(jnp.sum(execute_plan(plan, vals, b[i],
                                        EC(impl="pallas")) * w[i])
                   for i in range(BATCH))

    gv, gb = jax.grad(loss, argnums=(0, 1))(a.vals, bs)
    wv, wb = jax.grad(loss_loop, argnums=(0, 1))(a.vals, bs)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv), **TOL)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(wb), **TOL)


@pytest.mark.parametrize("method", METHODS)
def test_vmap_of_grad_per_example(method):
    """Per-example value-grads under vmap(grad) match the explicit stack."""
    a, bs, w = _case(seed=8)
    plan = build_plan(a, method=method)

    def one_loss(vals, b, wi):
        return jnp.sum(execute_plan(plan, vals, b, EC(impl="pallas")) * wi)

    per = jax.vmap(jax.grad(one_loss), in_axes=(None, 0, 0))(a.vals, bs, w)
    want = jnp.stack([jax.grad(one_loss)(a.vals, bs[i], w[i])
                      for i in range(BATCH)])
    np.testing.assert_allclose(np.asarray(per), np.asarray(want), **TOL)


def test_batched_grad_matches_dense_oracle():
    a, bs, w = _case(seed=9)
    plan = build_plan(a, method="merge")
    row_ptr, col_ind, shape = a.row_ptr, a.col_ind, a.shape

    def dense_loss(vals, b):
        dense = CSR(row_ptr, col_ind, vals, shape).to_dense()
        return jnp.sum(jnp.einsum("mk,bkn->bmn", dense, b) * w)

    gv, gb = jax.grad(
        lambda v, b: jnp.sum(execute_plan(plan, v, b, EC(impl="pallas")) * w),
        argnums=(0, 1))(a.vals, bs)
    wv, wb = jax.grad(dense_loss, argnums=(0, 1))(a.vals, bs)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(wb),
                               rtol=1e-4, atol=1e-5)


# -------------------------------------------------------------- K-tiling ---


@pytest.mark.parametrize("method", METHODS)
def test_ktile_bitmatches_whole_k(method):
    """Default tk covers small k in one panel == explicit whole-k panel,
    bit for bit (the unsplit kernel's exact dataflow)."""
    a = random_csr(jax.random.PRNGKey(10), 48, 96, nnz_per_row=(0, 12))
    b = jax.random.normal(jax.random.PRNGKey(11), (96, 128))
    plan = build_plan(a, method=method)
    o_default = execute_plan(plan, a.vals, b, EC(impl="pallas"))
    o_whole = execute_plan(plan, a.vals, b, EC(impl="pallas", tk=96))
    np.testing.assert_array_equal(np.asarray(o_default), np.asarray(o_whole))


@pytest.mark.parametrize("method", METHODS)
def test_ktile_bitmatch_on_mini_suite(method):
    """Acceptance: K-tiled kernels bit-match the whole-K dataflow on the
    mini corpus (every mini k fits one default panel)."""
    from repro.matrices.suites import get_suite
    rng = np.random.default_rng(23)
    for spec in get_suite("mini"):
        a = spec()
        vals = jnp.asarray(rng.standard_normal(a.nnz_pad), jnp.float32)
        b = jnp.asarray(rng.standard_normal((a.k, 128)), jnp.float32)
        plan = build_plan(a, method=method, with_transpose=False)
        o_default = execute_plan(plan, vals, b, EC(impl="pallas"))
        o_whole = execute_plan(plan, vals, b, EC(impl="pallas", tk=a.k))
        np.testing.assert_array_equal(np.asarray(o_default),
                                      np.asarray(o_whole), err_msg=spec.name)
        dense = CSR(a.row_ptr, a.col_ind, vals, a.shape).to_dense()
        np.testing.assert_allclose(np.asarray(o_default),
                                   np.asarray(dense @ b), rtol=3e-5,
                                   atol=3e-5, err_msg=spec.name)


@pytest.mark.parametrize("tk", [8, 24, 64])
@pytest.mark.parametrize("method", METHODS)
def test_ktile_stream_matches_oracle(method, tk):
    """Forcing multiple K panels (accumulator carry) stays correct."""
    a, bs, w = _case(seed=12, k=96, npr=(0, 20))
    plan = build_plan(a, method=method)
    got = execute_plan(plan, a.vals, bs, EC(impl="pallas", tk=tk))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_loop(plan, a.vals, bs, "pallas")),
                               **TOL)
    gv = jax.grad(lambda v: jnp.sum(
        execute_plan(plan, v, bs, EC(impl="pallas", tk=tk)) * w))(a.vals)
    wv = jax.grad(lambda v: jnp.sum(
        execute_plan(plan, v, bs, EC(impl="xla")) * w))(a.vals)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv), **TOL)


def test_resolve_tk_bounds_vmem():
    assert resolve_tk(64, None) == (64, 1)
    assert resolve_tk(65, None) == (72, 1)           # sublane-padded
    assert resolve_tk(29568, None) == (29568, 1)     # Qwen2-72B d_in
    assert resolve_tk(65536, None) == (65536, 1)     # the graph benchmark
    # Above the cap the panel still streams, in cap-sized K-tiles.
    tk, n_k = resolve_tk(M.DEFAULT_TK_MAX + 1, None)
    assert (tk, n_k) == (M.DEFAULT_TK_MAX, 2)
    assert resolve_tk(100, 16) == (16, 7)
    assert resolve_tk(100, 3) == (8, 13)             # sublane floor
    # Two float32 panel buffers stay within the panel's share of VMEM, and
    # the largest launch's limit, headroom included, within the VMEM.
    num, den = M.PANEL_SHARE
    assert 2 * M.DEFAULT_TK_MAX * M.TN * 4 <= M.V5E_VMEM_BYTES * num // den
    widest = M.launch_vmem_bytes(
        M.DEFAULT_TK_MAX, tm=M.TM, tn=M.TN, out_dtype=jnp.float32,
        acc_dtype=jnp.float32, bias_dtype=jnp.float32,
        residual_dtype=jnp.float32)
    assert widest + M.VMEM_HEADROOM_BYTES <= M.V5E_VMEM_BYTES


def test_vmem_limit_only_above_default_scope():
    """A launch asks for more scoped VMEM only when its blocks outgrow the
    default: the granite w2 panel (k=8192) does not, the graph's does."""

    def limit(k):
        tk, _ = resolve_tk(k, None)
        return M.vmem_limit(M.launch_vmem_bytes(
            tk, tm=M.TM, tn=M.TN, out_dtype=jnp.float32,
            acc_dtype=jnp.float32))

    assert limit(8192) is None
    got = limit(65536)
    assert 2 * 65536 * M.TN * 4 + M.VMEM_HEADROOM_BYTES <= got
    assert got <= M.V5E_VMEM_BYTES


@pytest.mark.parametrize("method", METHODS)
def test_launch_vmem_matches_static_model(method):
    """The VMEM a kernel sizes its limit from is the static launch model's,
    for every audited dtype/epilogue variant."""
    from repro.analysis.kernel_audit import _variants
    from repro.kernels.registry import get_method
    a = random_csr(jax.random.PRNGKey(5), 40, 4096, nnz_per_row=(0, 6))
    plan = build_plan(a, method=method)
    for var in _variants():
        (model,) = get_method(method).traffic(plan, 128, 1, var, None)
        ep = var.epilogue
        got = M.launch_vmem_bytes(
            resolve_tk(a.k, None)[0], tm=M.TM, tn=M.TN,
            out_dtype=var.out_dtype or var.b_dtype,
            acc_dtype=var.acc_dtype,
            bias_dtype=var.b_dtype if ep is not None and ep.bias else None,
            residual_dtype=var.b_dtype if ep is not None and ep.residual
            else None)
        assert got == model.vmem_bytes(), var.name


@pytest.mark.parametrize("method", METHODS)
def test_whole_k_panel_matches_ktiled_and_oracle(method):
    """k=4096 now runs as one resident panel by default; it matches the
    dense oracle and the forced 1024-row K-tiled path (four tiles)."""
    a = random_csr(jax.random.PRNGKey(13), 24, 4096, nnz_per_row=(0, 40))
    b = jax.random.normal(jax.random.PRNGKey(14), (4096, 128))
    plan = build_plan(a, method=method, with_transpose=False)
    assert resolve_tk(a.k, None) == (4096, 1)
    o_whole = execute_plan(plan, a.vals, b, EC(impl="pallas"))
    o_tiled = execute_plan(plan, a.vals, b, EC(impl="pallas", tk=1024))
    want = np.asarray(a.to_dense()) @ np.asarray(b)
    np.testing.assert_allclose(np.asarray(o_whole), want, **TOL)
    np.testing.assert_allclose(np.asarray(o_whole), np.asarray(o_tiled),
                               **TOL)


@pytest.mark.parametrize("method", METHODS)
def test_k_tiles_counter_counts_traces(method):
    """``spmm_kernel_k_tiles_total`` counts a launch once when it is traced,
    labelled with its K-tile count, and never again per call."""
    from repro import obs
    kernel = f"{method}_spmm"
    fam = obs.registry.get("spmm_kernel_k_tiles_total")
    count = lambda n_k: fam.labels(kernel=kernel, k_tiles=n_k).value
    a = random_csr(jax.random.PRNGKey(15), 24, 200, nnz_per_row=(0, 9))
    b = jax.random.normal(jax.random.PRNGKey(16), (200, 128))
    plan = build_plan(a, method=method, with_transpose=False)
    before = {n_k: count(n_k) for n_k in ("1", "25")}
    fwd = jax.jit(lambda v, b: execute_plan(plan, v, b, EC(impl="pallas")))
    tiled = jax.jit(lambda v, b: execute_plan(plan, v, b,
                                              EC(impl="pallas", tk=8)))
    fwd(a.vals, b).block_until_ready()
    tiled(a.vals, b).block_until_ready()
    after = {n_k: count(n_k) for n_k in ("1", "25")}
    assert after == {"1": before["1"] + 1, "25": before["25"] + 1}
    fwd(a.vals, b).block_until_ready()
    tiled(a.vals * 2, b).block_until_ready()
    assert {n_k: count(n_k) for n_k in ("1", "25")} == after


# --------------------------------------------------- degenerate patterns ---


def _degenerates():
    return {
        "zero_nnz": CSR(jnp.zeros(5, jnp.int32), jnp.zeros(0, jnp.int32),
                        jnp.zeros(0), (4, 8)),
        "pad_only": CSR(jnp.zeros(5, jnp.int32), jnp.zeros(3, jnp.int32),
                        jnp.zeros(3), (4, 8)),
        "zero_rows": CSR(jnp.zeros(1, jnp.int32), jnp.zeros(2, jnp.int32),
                         jnp.zeros(2), (0, 8)),
        "one_row": random_csr(jax.random.PRNGKey(13), 1, 8, nnz_per_row=4),
    }


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("name", sorted(_degenerates()))
def test_degenerate_forward_and_grad(name, method, impl):
    """0-nnz, 0-row, and 1-row patterns execute and differentiate, 2-D and
    batched (the sddmm 0-nnz reshape crash and m=0 plan crash, ISSUE 3)."""
    a = _degenerates()[name]
    b = jax.random.normal(jax.random.PRNGKey(14), (8, 16))
    bs = jax.random.normal(jax.random.PRNGKey(15), (2, 8, 16))
    dense = np.asarray(a.to_dense())
    plan = build_plan(a, method=method)
    got = execute_plan(plan, a.vals, b, EC(impl=impl))
    np.testing.assert_allclose(np.asarray(got), dense @ np.asarray(b), **TOL)
    got3 = execute_plan(plan, a.vals, bs, EC(impl=impl))
    assert got3.shape == (2, a.m, 16)
    w = jnp.ones((2, a.m, 16))
    gv, gb = jax.grad(
        lambda v, bb: jnp.sum(execute_plan(plan, v, bb, EC(impl=impl)) * w),
        argnums=(0, 1))(a.vals, bs)
    assert gv.shape == a.vals.shape and gb.shape == bs.shape
    nnz = int(np.asarray(a.row_ptr)[-1])
    assert not np.any(np.asarray(gv)[nnz:]), \
        "padded values received nonzero cotangents"


def test_degenerate_through_spmm_api():
    for name, a in _degenerates().items():
        b = jax.random.normal(jax.random.PRNGKey(16), (8, 16))
        got = spmm(a, b, exec=EC(impl="xla"))
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(a.to_dense()) @ np.asarray(b),
                                   err_msg=name, **TOL)


# ------------------------------------------- silent-wrong-answer guards ---


def test_undersized_l_pad_raises():
    """A 16-long row with l_pad=8 must raise, not silently truncate."""
    a = random_csr(jax.random.PRNGKey(17), 8, 32, nnz_per_row=16)
    b = jax.random.normal(jax.random.PRNGKey(18), (32, 8))
    with pytest.raises(ValueError, match="silently drop"):
        build_plan(a, method="rowsplit", l_pad=8)
    with pytest.raises(ValueError, match="silently drop"):
        spmm(a, b, PlanPolicy(method="rowsplit", l_pad=8))
    with pytest.raises(ValueError, match="silently drop"):
        spmm(a, b, PlanPolicy(method="rowsplit", l_pad=8), plan="inline")
    # exact bound and larger are fine
    for lp in (16, 24):
        got = spmm(a, b, PlanPolicy(method="rowsplit", l_pad=lp),
                   EC(impl="xla"))
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.spmm_dense_ref(a, b)),
                                   **TOL)


def test_plan_override_conflicts_raise():
    a, bs, _ = _case(seed=19)
    b = bs[0]
    plan = build_plan(a, method="merge")
    with pytest.raises(ValueError, match="conflict"):
        spmm(a, b, PlanPolicy(method="rowsplit"), plan=plan)
    with pytest.raises(ValueError, match="conflict"):
        spmm(a, b, PlanPolicy(t=plan.meta.t + 1), plan=plan)
    with pytest.raises(ValueError, match="conflict"):
        spmm(a, b, PlanPolicy(l_pad=64), plan=plan)
    # agreeing overrides execute fine
    got = spmm(a, b, PlanPolicy(method="merge", t=plan.meta.t),
               EC(impl="xla"), plan=plan)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.spmm_dense_ref(a, b)), **TOL)
    rplan = build_plan(a, method="rowsplit")
    with pytest.raises(ValueError, match="conflict"):
        spmm(a, b, PlanPolicy(l_pad=(rplan.meta.l_pad or 0) + 1),
             plan=rplan)


def test_inline_batched_b_raises():
    a, bs, _ = _case(seed=20)
    with pytest.raises(ValueError, match="prebuilt plan"):
        spmm(a, bs, PlanPolicy(method="merge"), plan="inline")


# ------------------------------------------------- SparseLinear batching ---


def test_sparse_linear_batched_path_matches_flat(monkeypatch):
    rng = np.random.default_rng(21)
    w = jnp.asarray(rng.standard_normal((24, 32)), jnp.float32)
    sl = S.SparseLinear.from_dense(w, 0.25)
    x = jnp.asarray(rng.standard_normal((2, 5, 24)), jnp.float32)
    flat = sl(x, EC(impl="xla"))
    monkeypatch.setattr(S, "BATCHED_MIN_TOKENS", 1)
    for impl in IMPLS:
        np.testing.assert_allclose(np.asarray(sl(x, EC(impl=impl))),
                                   np.asarray(flat), **TOL)
    g_b = jax.grad(lambda xx: jnp.sum(sl(xx, EC(impl="xla")) ** 2))(x)
    monkeypatch.setattr(S, "BATCHED_MIN_TOKENS", 128)
    g_f = jax.grad(lambda xx: jnp.sum(sl(xx, EC(impl="xla")) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g_b), np.asarray(g_f), **TOL)


def test_sparse_linear_vmap():
    """jax.vmap over a SparseLinear call is first-class."""
    rng = np.random.default_rng(22)
    w = jnp.asarray(rng.standard_normal((16, 24)), jnp.float32)
    sl = S.SparseLinear.from_dense(w, 0.3)
    x = jnp.asarray(rng.standard_normal((4, 6, 16)), jnp.float32)
    got = jax.vmap(lambda xi: sl(xi, EC(impl="pallas")))(x)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(sl(x, EC(impl="xla"))), **TOL)


# ----------------------------------------------------------- microbatching ---


def test_microbatched_runner():
    from repro.runtime import steps as R
    calls = []

    @jax.jit
    def fn(x, y):
        return {"out": x * 2.0 + y}

    def counted(x, y):
        calls.append(x.shape)
        return fn(x, y)

    x = jnp.arange(12.0).reshape(6, 2)
    y = jnp.ones((2,))
    run = R.microbatched(counted, 2, argnums=(0,))
    out = run(x, y)
    np.testing.assert_allclose(np.asarray(out["out"]),
                               np.asarray(x) * 2 + 1)
    assert calls == [(2, 2)] * 3
    strict = R.microbatched(counted, 2, argnums=(0,), pad=False)
    with pytest.raises(ValueError, match="does not divide"):
        strict(jnp.ones((5, 2)), y)
    with pytest.raises(ValueError, match="positive"):
        R.microbatched(fn, 0)
