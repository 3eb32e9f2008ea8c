"""Static-analysis subsystem tests (ISSUE 8).

The core of the coverage is *invariant mutation*: take a valid plan,
corrupt exactly one checked invariant, and assert the plan linter fires
the specific diagnostic for it — so each check is proven live, not just
present.  Plus: the REPRO_VERIFY_PLANS hook gating, kernel-audit model
checks and loud coverage failure, repo-lint rules on synthetic sources,
and the CLI exit codes.
"""
from __future__ import annotations

import dataclasses
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import planlint, set_verify_plans
from repro.analysis.planlint import (PlanVerificationError, check_plan,
                                     verify_csr, verify_plan,
                                     verify_sharded_plan)
from repro.core.config import PlanPolicy, ShardSpec
from repro.core.csr import CSR, random_csr
from repro.core.plan import PlanMeta, build_plan
from repro.distributed.spmm import build_sharded_plan


def codes(diags):
    return {d.code for d in diags}


@pytest.fixture(scope="module")
def a():
    # m=41 (not a TM multiple) so the ELL structures carry padding rows,
    # and nnz_pad > nnz so the dead-slot range (P022) is non-empty.
    key = jax.random.PRNGKey(7)
    a0 = random_csr(key, 41, 96, nnz_per_row=(1, 17))
    nnz = int(np.asarray(a0.row_ptr)[-1])
    return random_csr(key, 41, 96, nnz_per_row=(1, 17), pad_to=nnz + 8)


@pytest.fixture(scope="module")
def merge_plan(a):
    return build_plan(a, method="merge")


@pytest.fixture(scope="module")
def rowsplit_plan(a):
    return build_plan(a, method="rowsplit")


@pytest.fixture(scope="module")
def rowgroup_plan(a):
    return build_plan(a, method="rowgroup")


def with_fwd(plan, **over):
    fwd = dict(plan.fwd)
    fwd.update(over)
    return dataclasses.replace(plan, fwd=fwd)


# ------------------------------------------------------------- clean runs ---


def test_clean_plans_verify(a, merge_plan, rowsplit_plan, rowgroup_plan):
    for plan in (merge_plan, rowsplit_plan, rowgroup_plan):
        assert verify_plan(plan, a) == []
        assert verify_plan(plan) == []      # CSR-free path too


def test_clean_sharded_verifies(a):
    for dim in ("rows", "cols"):
        plan = build_sharded_plan(a, PlanPolicy(shards=ShardSpec(
            n=3, dim=dim)))
        assert verify_sharded_plan(plan, a) == []


# -------------------------------------------------- CSR-level corruption ---


def test_non_monotone_row_ptr_p001(a):
    rp = np.asarray(a.row_ptr).copy()
    rp[2], rp[3] = rp[3] + 1, rp[2]
    bad = CSR(jnp.asarray(rp), a.col_ind, a.vals, a.shape)
    assert "P001" in codes(verify_csr(bad))


def test_col_ind_out_of_range_p002(a):
    ci = np.asarray(a.col_ind).copy()
    ci[0] = a.shape[1] + 5
    bad = CSR(a.row_ptr, jnp.asarray(ci), a.vals, a.shape)
    assert "P002" in codes(verify_csr(bad))


def test_plan_csr_mismatch_p003(merge_plan):
    other = random_csr(jax.random.PRNGKey(8), 8, 8, nnz_per_row=2)
    assert "P003" in codes(verify_plan(merge_plan, other))


# ---------------------------------------------- slot coverage corruption ---


def test_duplicate_slot_p020(a, merge_plan):
    slot = np.asarray(merge_plan.fwd["slot_nz"]).copy()
    live = np.argwhere(slot < merge_plan.meta.nnz_pad)
    (r0, c0), (r1, c1) = live[0], live[1]
    slot[r1, c1] = slot[r0, c0]             # one nonzero consumed twice
    diags = verify_plan(with_fwd(merge_plan, slot_nz=jnp.asarray(slot)), a)
    assert "P020" in codes(diags)
    assert "P021" in codes(diags)           # ...and one now missing


def test_sentinel_aimed_at_live_data_p020(a, merge_plan):
    # A sentinel slot redirected at live values double-counts a nonzero:
    # exactly the corruption the exactly-once invariant exists for.
    slot = np.asarray(merge_plan.fwd["slot_nz"]).copy()
    sent = np.argwhere(slot == merge_plan.meta.nnz_pad)
    assert len(sent), "merge structure always pads the last chunk"
    r, c = sent[0]
    slot[r, c] = 0
    diags = verify_plan(with_fwd(merge_plan, slot_nz=jnp.asarray(slot)), a)
    assert "P020" in codes(diags)


def test_missing_nonzero_p021(a, merge_plan):
    slot = np.asarray(merge_plan.fwd["slot_nz"]).copy()
    live = np.argwhere(slot < merge_plan.meta.nnz_pad)
    r0, c0 = live[0]
    slot[r0, c0] = merge_plan.meta.nnz_pad      # retired to sentinel
    diags = verify_plan(with_fwd(merge_plan, slot_nz=jnp.asarray(slot)), a)
    assert "P021" in codes(diags)


def test_out_of_range_slot_p022(a, merge_plan):
    slot = np.asarray(merge_plan.fwd["slot_nz"]).copy()
    slot[0, 0] = merge_plan.meta.nnz_pad + 3    # past even the sentinel
    diags = verify_plan(with_fwd(merge_plan, slot_nz=jnp.asarray(slot)), a)
    assert "P022" in codes(diags)


def test_dead_range_slot_p022(a, merge_plan):
    # In-range as an index but pointing at padding values (nnz..nnz_pad):
    # reads a stale value, not a zero — distinct from the sentinel.
    nnz = int(np.asarray(a.row_ptr)[-1])
    if nnz == merge_plan.meta.nnz_pad:
        pytest.skip("pattern has no dead padding range")
    slot = np.asarray(merge_plan.fwd["slot_nz"]).copy()
    sent = np.argwhere(slot == merge_plan.meta.nnz_pad)
    r, c = sent[0]
    slot[r, c] = nnz                            # first dead slot
    diags = verify_plan(with_fwd(merge_plan, slot_nz=jnp.asarray(slot)), a)
    assert "P022" in codes(diags)


# -------------------------------------------------- merge-path corruption ---


def test_double_covered_merge_tile_p030_p031(a, merge_plan):
    tile = np.asarray(merge_plan.fwd["tile"]).copy()
    tile[1:] = tile[:-1][::-1][: len(tile) - 1]  # scrambled, decreasing
    diags = verify_plan(with_fwd(merge_plan, tile=jnp.asarray(tile)), a)
    assert codes(diags) & {"P030", "P031", "P032"}


def test_tile_skipped_p031(a, merge_plan):
    tile = np.asarray(merge_plan.fwd["tile"]).copy()
    n_tiles = -(-merge_plan.meta.m // planlint._TM)
    if n_tiles < 2:
        pytest.skip("needs >= 2 row tiles")
    tile[tile == 1] = 0                          # tile 1 never visited
    diags = verify_plan(with_fwd(merge_plan, tile=jnp.asarray(tile)), a)
    assert "P031" in codes(diags)


def test_wrong_first_last_flags_p031(a, merge_plan):
    first = np.asarray(merge_plan.fwd["first"]).copy()
    first[0] = 0                                  # chunk 0 must start a tile
    diags = verify_plan(with_fwd(merge_plan, first=jnp.asarray(first)), a)
    assert "P031" in codes(diags)


def test_lrow_scatters_to_wrong_row_p032(a, merge_plan):
    lrow = np.asarray(merge_plan.fwd["lrow"]).copy()
    slot = np.asarray(merge_plan.fwd["slot_nz"])
    live = np.argwhere(slot < merge_plan.meta.nnz_pad)
    r0, c0 = live[0]
    lrow[r0, c0] = (lrow[r0, c0] + 1) % planlint._TM
    diags = verify_plan(with_fwd(merge_plan, lrow=jnp.asarray(lrow)), a)
    assert "P032" in codes(diags)


# ------------------------------------------- rowsplit / rowgroup mutation ---


def test_truncated_l_pad_p040(a, rowsplit_plan):
    meta = dataclasses.replace(rowsplit_plan.meta,
                               l_pad=rowsplit_plan.meta.l_pad - 1)
    bad = dataclasses.replace(rowsplit_plan, meta=meta)
    assert "P040" in codes(verify_plan(bad, a))


def test_ell_slot_wrong_row_p041(a, rowsplit_plan):
    slot = np.asarray(rowsplit_plan.fwd["slot_nz"]).copy()
    nnz_pad = rowsplit_plan.meta.nnz_pad
    rows_live = [r for r in range(slot.shape[0])
                 if (slot[r] < nnz_pad).any()]
    r0, r1 = rows_live[0], rows_live[1]
    c0 = int(np.argwhere(slot[r0] < nnz_pad)[0, 0])
    c1 = int(np.argwhere(slot[r1] < nnz_pad)[0, 0])
    slot[r0, c0], slot[r1, c1] = slot[r1, c1], slot[r0, c0]
    diags = verify_plan(
        with_fwd(rowsplit_plan, slot_nz=jnp.asarray(slot)), a)
    assert "P041" in codes(diags)


def test_live_slot_on_padding_row_p042(a, rowsplit_plan):
    slot = np.asarray(rowsplit_plan.fwd["slot_nz"]).copy()
    if slot.shape[0] <= rowsplit_plan.meta.m:
        pytest.skip("no tile-padding rows on this shape")
    slot[-1, 0] = 0                               # pad row reads live data
    diags = verify_plan(
        with_fwd(rowsplit_plan, slot_nz=jnp.asarray(slot)), a)
    assert "P042" in codes(diags)


def test_rowgroup_bad_group_table_p050(a, rowgroup_plan):
    extra = list(rowgroup_plan.meta.extra)
    (m_g, l_g) = extra[0]
    extra[0] = (m_g + 1, l_g)                     # counts no longer sum to m
    meta = dataclasses.replace(rowgroup_plan.meta, extra=tuple(extra))
    bad = dataclasses.replace(rowgroup_plan, meta=meta)
    assert "P050" in codes(verify_plan(bad, a))


def test_rowgroup_non_permutation_p051(a, rowgroup_plan):
    inv = np.asarray(rowgroup_plan.fwd["inv_pos"]).copy()
    inv[1] = inv[0]                               # two rows, one source
    diags = verify_plan(
        with_fwd(rowgroup_plan, inv_pos=jnp.asarray(inv)), a)
    assert "P051" in codes(diags)


# ------------------------------------------------------ bwd-plan mutation ---


def test_bwd_missing_vs_meta_p060(a, merge_plan):
    bad = dataclasses.replace(merge_plan, bwd=None)
    assert "P060" in codes(verify_plan(bad, a))


def test_bwd_coverage_corruption_p021(a, merge_plan):
    bwd = dict(merge_plan.bwd)
    slot = np.asarray(bwd["slot_nz"]).copy()
    live = np.argwhere(slot < merge_plan.meta.nnz_pad)
    r0, c0 = live[0]
    slot[r0, c0] = merge_plan.meta.nnz_pad
    bwd["slot_nz"] = jnp.asarray(slot)
    bad = dataclasses.replace(merge_plan, bwd=bwd)
    diags = verify_plan(bad, a)
    assert any(d.code == "P021" and "bwd" in d.where for d in diags)


# ------------------------------------------------------- sharded mutation ---


def test_sharded_bounds_dont_tile_p070(a):
    plan = build_sharded_plan(a, PlanPolicy(shards=ShardSpec(n=2)))
    bounds = list(plan.meta.bounds)
    bounds[1] += 1
    meta = dataclasses.replace(plan.meta, bounds=tuple(bounds))
    bad = dataclasses.replace(plan, meta=meta)
    assert codes(verify_sharded_plan(bad, a)) & {"P070", "P071", "P072"}


def test_sharded_gather_not_exactly_once_p072(a):
    plan = build_sharded_plan(a, PlanPolicy(shards=ShardSpec(n=2)))
    vs = [np.asarray(v).copy() for v in plan.vals_slots]
    nnz_pad = plan.meta.nnz_pad
    live = np.argwhere(vs[0] < nnz_pad)
    vs[0][tuple(live[0])] = nnz_pad               # drop one global nonzero
    bad = dataclasses.replace(
        plan, vals_slots=tuple(jnp.asarray(v) for v in vs))
    assert "P072" in codes(verify_sharded_plan(bad, a))


def test_sharded_bad_b_rows_p074(a):
    plan = build_sharded_plan(
        a, PlanPolicy(shards=ShardSpec(n=2, dim="cols")))
    br = [np.asarray(v).copy() for v in plan.b_rows]
    live = np.argwhere(br[0] < a.shape[1])
    br[0][tuple(live[0])] += 1
    bad = dataclasses.replace(
        plan, b_rows=tuple(jnp.asarray(v) for v in br))
    assert "P074" in codes(verify_sharded_plan(bad, a))


def test_sharded_uniform_flag_lie_p073(a):
    plan = build_sharded_plan(a, PlanPolicy(shards=ShardSpec(n=2)))
    metas = list(plan.meta.local_metas)
    metas[0] = dataclasses.replace(metas[0], t=metas[0].t * 2)
    meta = dataclasses.replace(plan.meta, uniform=True,
                               local_metas=tuple(metas))
    bad = dataclasses.replace(plan, meta=meta)
    assert codes(verify_sharded_plan(bad)) & {"P073", "P071", "P003"}


# ------------------------------------------------------- hook + eager meta ---


def test_unhashable_extra_raises_eagerly():
    with pytest.raises(TypeError, match="hashable"):
        PlanMeta(method="merge", shape=(4, 4), nnz_pad=4, t=16, tl=16,
                 l_pad=None, has_transpose=False, extra=[1, 2])


def test_verify_hook_gating(a, monkeypatch):
    built = {}
    prev = set_verify_plans(False)
    try:
        build_plan(a, method="merge")         # off: no verification runs
        set_verify_plans(True)
        plan = build_plan(a, method="merge")  # on: clean plan passes
        built["plan"] = plan
    finally:
        set_verify_plans(prev)
    assert built["plan"].meta.method == "merge"


def test_verify_hook_env_var():
    import subprocess
    import sys
    code = ("from repro.analysis import _flags; "
            "raise SystemExit(0 if _flags.verify_plans else 1)")
    env = dict(os.environ, REPRO_VERIFY_PLANS="1",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    assert subprocess.run([sys.executable, "-c", code],
                          env=env).returncode == 0


def test_check_plan_raises_with_diagnostics(a, merge_plan):
    slot = np.asarray(merge_plan.fwd["slot_nz"]).copy()
    live = np.argwhere(slot < merge_plan.meta.nnz_pad)
    slot[tuple(live[0])] = merge_plan.meta.nnz_pad
    bad = with_fwd(merge_plan, slot_nz=jnp.asarray(slot))
    with pytest.raises(PlanVerificationError) as ei:
        check_plan(bad, a)
    assert "P021" in {d.code for d in ei.value.diagnostics}


# ------------------------------------------------------------ kernel audit ---


def test_audit_all_clean():
    from repro.analysis import kernel_audit
    rows, diags = kernel_audit.audit_all()
    assert diags == []
    from repro.kernels import registry
    covered = {(r.method, r.impl) for r in rows}
    for name in registry.method_names():
        for impl in kernel_audit.AUDIT_IMPLS:
            assert (name, impl) in covered
    report = kernel_audit.format_report(rows, diags)
    assert "no findings" in report


def test_audit_fails_loudly_on_uncovered_method():
    from repro.analysis import kernel_audit
    from repro.kernels import registry
    spec = registry.get_method("merge")
    # replace() would inherit merge's traffic hook — a ghost method with
    # no launch model anywhere must trip K001.
    ghost = dataclasses.replace(spec, name="ghost", traffic=None)
    registry.register_method(ghost)
    try:
        rows, diags = kernel_audit.audit_all()
        assert "K001" in {d.code for d in diags}
        assert any("ghost" in d.where for d in diags)
    finally:
        registry._REGISTRY.pop("ghost", None)


def test_audit_stale_model_k002():
    from repro.analysis import kernel_audit
    kernel_audit.register_audit("no_such_method", lambda *a: [])
    try:
        _, diags = kernel_audit.audit_all()
        assert "K002" in {d.code for d in diags}
    finally:
        kernel_audit._AUDITS.pop("no_such_method", None)


def test_audit_single_writer_catches_double_flush():
    from repro.analysis.kernel_audit import Block, LaunchModel, \
        check_single_writer
    out = Block("out", (1, 8, 128), "float32",
                lambda i, j: (0, 0, 0), (1, 8, 128), "out")
    model = LaunchModel("bad", grid=(2, 2), blocks=(out,),
                        flush=lambda i, j: True, out=out)
    assert check_single_writer(model)         # 4 writes to one tile
    good = LaunchModel("good", grid=(2, 2), blocks=(out,),
                       flush=lambda i, j: (i, j) == (1, 1), out=out)
    assert check_single_writer(good) == []


def test_audit_in_bounds_catches_overrun():
    from repro.analysis.kernel_audit import Block, LaunchModel, \
        check_in_bounds
    blk = Block("b", (8, 128), "float32", lambda i: (i, 0),
                (16, 128), "in")
    ok = LaunchModel("ok", grid=(2,), blocks=(blk,),
                     flush=lambda i: True, out=blk)
    assert check_in_bounds(ok) == []
    bad = LaunchModel("bad", grid=(3,), blocks=(blk,),
                      flush=lambda i: True, out=blk)
    assert check_in_bounds(bad)


def test_audit_vmem_budget_flags_blowup():
    from repro.analysis.kernel_audit import (SMEM_BUDGET_BYTES,
                                             VMEM_BUDGET_BYTES, Block,
                                             LaunchModel, merge_chunk_ceiling,
                                             merge_smem_bytes)
    from repro.kernels.merge_spmm import T_MAX
    # A 16 MiB block, double-buffered, overflows the 16 MiB VMEM model and
    # counts nothing against SMEM.
    big = Block("b", (4096, 1024), "float32", lambda i: (0, 0),
                (4096, 1024), "in")
    model = LaunchModel("big", grid=(1,), blocks=(big,),
                        flush=lambda i: True, out=big)
    assert model.vmem_bytes() > VMEM_BUDGET_BYTES["tpu"]
    assert model.smem_bytes() == 0
    # The documented merge ceiling is consistent: one chunk more than the
    # ceiling overflows the 1 MiB SMEM model.
    c = merge_chunk_ceiling()
    assert merge_smem_bytes(c, T_MAX) <= SMEM_BUDGET_BYTES["tpu"] \
        < merge_smem_bytes(c + 1, T_MAX)


# --------------------------------------------------------------- repo lint ---


def _lint_src(tmp_path, source, name="mod.py"):
    from repro.analysis import lint
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return lint.lint_file(str(p))


def test_rl001_host_sync_in_jit(tmp_path):
    diags = _lint_src(tmp_path, """
        import jax, numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x) + x.item()

        def host_only(x):
            return float(np.asarray(x))    # fine: not jit-reachable
    """)
    assert [d.code for d in diags] == ["RL001", "RL001"]


def test_rl001_kernel_body_and_defvjp(tmp_path):
    diags = _lint_src(tmp_path, """
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            i = pl.program_id(0)
            o_ref[...] = float(i)

        def bwd(res, ct):
            return np.asarray(ct)

        op.defvjp(kernel, bwd)
    """)
    assert {d.code for d in diags} == {"RL001"}
    assert len(diags) == 2


def test_rl001_noqa_suppresses(tmp_path):
    diags = _lint_src(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return x.item()    # noqa: RL001
    """)
    assert diags == []


def test_rl002_legacy_kwargs(tmp_path):
    diags = _lint_src(tmp_path, """
        from repro import spmm
        c = spmm(a, b, method="merge", interpret=True)
        d = spmm(a, b, policy)                 # v1 spelling: clean
        e = get_plan(a, l_pad=32)
    """)
    assert [d.code for d in diags] == ["RL002", "RL002"]


def test_rl002_test_api_exempt(tmp_path):
    from repro.analysis import lint
    sub = tmp_path / "tests"
    sub.mkdir()
    p = sub / "test_api.py"
    p.write_text("spmm(a, b, method='merge')\n")
    assert lint.lint_file(str(p)) == []


def test_rl003_incomplete_methodspec(tmp_path):
    diags = _lint_src(tmp_path, """
        spec = MethodSpec(name="x", description="d", build_structure=f,
                          execute=g, inline=h)
        ok = registry.MethodSpec(
            name="y", description="d", build_structure=f, execute=g,
            inline=h, resolve_params=r, tune_candidates=None,
            heuristic_rank=None, traffic=None)
    """)
    assert [d.code for d in diags] == ["RL003"]
    assert "resolve_params" in diags[0].message


def test_rl004_unregistered_bench(tmp_path):
    from repro.analysis import lint
    bench = tmp_path / "benchmarks"
    bench.mkdir()
    (bench / "run.py").write_text(textwrap.dedent("""
        def _mods():
            from . import bench_a
            return [("a", bench_a)]
    """))
    (bench / "bench_a.py").write_text("")
    (bench / "bench_orphan.py").write_text("")
    diags = []
    lint.check_bench_registration(str(bench), diags)
    assert [d.code for d in diags] == ["RL004"]
    assert "bench_orphan" in diags[0].message


def test_repo_lint_is_clean():
    from repro.analysis import lint
    root = os.path.join(os.path.dirname(__file__), "..")
    diags = lint.run_lint(repo_root=os.path.abspath(root))
    assert diags == [], "\n".join(str(d) for d in diags)


# ---------------------------------------------------------------- CLI glue ---


def test_cli_lint_exit_codes(tmp_path):
    from repro.analysis import cli
    assert cli.run_repo_lint(None) == 0
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n@jax.jit\ndef f(x):\n    return x.item()\n")
    assert cli.run_repo_lint([str(bad)]) == 1


def test_cli_planlint_smoke(a, capsys):
    from repro.analysis import cli
    from repro.matrices import suites
    suites.register_spec(suites.MatrixSpec(
        name="_analysis_smoke", build=lambda: a, family="synthetic"))
    suites.register_suite("_analysis_smoke", ("_analysis_smoke",))
    try:
        assert cli.run_planlint("_analysis_smoke") == 0
        assert "verified" in capsys.readouterr().out
    finally:
        suites._SUITES.pop("_analysis_smoke", None)
        suites._SPECS.pop("_analysis_smoke", None)


# ----------------------------------------------- property-based round trip ---


def test_hypothesis_roundtrip_mini_suite():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    from repro.kernels import registry
    from repro.matrices.suites import get_suite

    specs = list(get_suite("mini"))
    plans = {}

    @hyp.settings(max_examples=len(specs) * len(registry.method_names()),
                  deadline=None)
    @hyp.given(i=st.integers(0, len(specs) - 1),
               method=st.sampled_from(sorted(registry.method_names())))
    def roundtrip(i, method):
        spec = specs[i]
        key = (spec.name, method)
        if key not in plans:
            a = spec.build()
            plans[key] = (a, build_plan(a, method=method))
        a, plan = plans[key]
        assert verify_plan(plan, a) == []

    roundtrip()
