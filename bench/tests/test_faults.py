"""With the timed path broken underneath, a run's ``correct`` comes out
false; and the control, the reference one precision step down in the
program's place, fails the cell's limits.

Each fault is planted in the program, under the harness, before set-up
builds the programs the window drives, at tiny size on the CPU.
"""
import pytest

from conftest import run_cell


def _swap_first_positions(monkeypatch):
    """An answer altered where it is produced: the pruned forward's
    logits at position 0 replaced by those of position 1."""
    from repro.launch import serve
    orig = serve.make_pruned_forward

    def broken(cfg):
        fwd = orig(cfg)

        def f(params, blocks, tokens):
            out = fwd(params, blocks, tokens)
            return out.at[:, 0].set(out[:, 1])
        return f
    monkeypatch.setattr(serve, "make_pruned_forward", broken)


def _alter_a_row(monkeypatch):
    """An answer altered where it is produced: one added to the merge
    kernel's output for one row of C (the row may be empty, so an
    output left at zero would not show)."""
    from repro.kernels import merge_spmm
    orig = merge_spmm.merge_spmm_pallas

    def broken(*a, **kw):
        out = orig(*a, **kw)
        return out.at[:, 5, :].add(1.0)
    monkeypatch.setattr(merge_spmm, "merge_spmm_pallas", broken)


@pytest.mark.parametrize("cell,plant", [
    ("granite-3-2b.score-128", _swap_first_positions),
    ("graph500-s16.agg-f128", _alter_a_row),
])
def test_altered_answer_is_not_correct(tiny_root, monkeypatch, cell, plant):
    import jax
    plant(monkeypatch)
    # Traces of the program's jitted wrappers outlive a run; drop them so
    # that set-up traces the planted fault, and again after it.
    jax.clear_caches()
    try:
        rc, res = run_cell(tiny_root, cell, seed=99)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert rc == 0
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", ["granite-3-2b.score-128",
                                  "graph500-s16.agg-f128"])
def test_control_fails_the_limits(tiny_root, cell):
    """The reference at the precision below the configuration's stands in
    for the program: at least one compared number passes its limit."""
    from bench import harness
    c = harness.resolve_cell(tiny_root, cell)
    harness.prepare_jax(tiny_root)
    cls = harness.system_class(c)
    system = cls(c.config, c.traffic, 5, harness.SetupClock())
    system.setup()
    sampler = harness.Sampler(int(c.traffic["sample"]), 5)
    harness.LOOPS[c.traffic["loop"]](system, 1.0, sampler)
    samples = sampler.sample()
    system.release()
    sound = system.check(samples)
    control = system.check(samples, quant=cls.CONTROL)
    limits = {k: v["limit"] for k, v in c.limits.items()}
    assert all(sound[k] <= limits[k] for k in limits), sound
    assert any(control[k] > limits[k] for k in limits), control
