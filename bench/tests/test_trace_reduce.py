"""The reduction from a profiler trace to busy time, kernel time, idle
gaps and the metrics that read them."""
import dataclasses
import os

import pytest

from bench import readers, trace_reduce, work
from bench.trace_reduce import Op, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _trace():
    # Window 0..100 ns; ops overlap at 10..30 and 20..40, then 60..70.
    # Host: the window, a wait span 0..45 and a dispatch span 45..100.
    k = 'custom_call_target="tpu_custom_call"'
    ops = [Op(f"%rowsplit_execute.3 = f32[8] custom-call(), {k}", 10, 30,
              {}),
           Op("%fusion.1 = f32[8] fusion()", 20, 40, {}),
           Op(f"%merge_execute.9 = f32[8] custom-call(), {k}", 60, 70, {}),
           Op(f"%flash.2 = f32[8] custom-call(), {k}", 95, 130, {})]
    host = [Op("bench.window", 0, 100, {}), Op("bench.wait", 0, 45, {}),
            Op("bench.dispatch", 45, 100, {})]
    return Trace(window=(0.0, 100.0), devices=[ops], host=host)


def test_busy_is_the_union_clipped_to_the_window():
    t = _trace()
    assert t.busy_intervals(0) == [(10, 40), (60, 70), (95, 100)]
    assert t.busy_s == pytest.approx(45e-9)
    assert t.window_s == pytest.approx(100e-9)


def test_kernel_time_matches_names_and_stats():
    t = _trace()
    assert t.op_seconds(readers.is_spmm_kernel) == pytest.approx(30e-9)
    assert sum(map(readers.is_spmm_kernel, t.devices[0])) == 2


def test_idle_gaps_are_named_by_the_innermost_host_span():
    t = _trace()
    gaps = dict(t.idle_gaps())
    # Gap 0..10 (midpoint under bench.wait); 40..60 and 70..95 (midpoints
    # under bench.dispatch).
    assert gaps["bench.wait"] == pytest.approx(10e-9)
    assert gaps["bench.dispatch"] == pytest.approx(45e-9)
    top = t.top_ops(4)
    assert [n for n, _ in top] == [
        "rowsplit_execute", "fusion", "merge_execute", "flash"]
    assert top[3][1] == pytest.approx(5e-9)      # clipped at the close
    assert trace_reduce.op_kind("%copy-start = (f32[8]) copy-start()") \
        == "copy-start"


def test_roofline_and_idle_readers():
    @dataclasses.dataclass
    class W:
        spmm: list

    @dataclasses.dataclass
    class R:
        trace: Trace
        window: W
        peak: dict
        chips: int = 1

    call = work.SpmmCall(m=8, k=8, nnz=16, n=128, val_bytes=4, b_bytes=4,
                         c_bytes=4)
    run = R(_trace(), W([call]), PEAK)
    least = work.spmm_least_seconds([call], PEAK)
    assert readers.spmm_roofline(run) == pytest.approx(
        100 * least / 30e-9)
    assert readers.idle_share(run) == pytest.approx(55.0)
    two = R(_trace(), W([dataclasses.replace(call, n=64), call]), PEAK)
    both = work.spmm_least_seconds(two.window.spmm, PEAK)
    assert readers.spmm_roofline(two) == pytest.approx(100 * both / 30e-9)
    run.trace.devices = [[]]
    assert readers.idle_share(run) is None
    assert readers.spmm_roofline(run) is None


@pytest.mark.parametrize("chips", [2, 4])
def test_shares_read_over_all_the_cells_chips(chips):
    """The same work on ``chips`` chips, each busy for 1/chips of one
    chip's kernel time and window: every share reads what one chip's
    does."""
    from bench.harness import load_module

    @dataclasses.dataclass
    class W:
        spmm: list
        flops: float
        seconds: float

    @dataclasses.dataclass
    class R:
        trace: Trace
        window: W
        peak: dict
        chips: int

    k = 'custom_call_target="tpu_custom_call"'

    def run(n):
        ops = [Op(f"%rowsplit_execute.3 = f32[8] custom-call(), {k}", 10,
                  10 + 400 / n, {})]
        call = work.SpmmCall(m=4096, k=4096, nnz=65536, n=128, val_bytes=4,
                             b_bytes=2, c_bytes=2)
        return R(Trace(window=(0.0, 1000.0 / n), devices=[ops] * n,
                       host=[]),
                 W([call], 1e9, 1e-6 / n), PEAK, n)

    mfu = load_module(os.path.join(os.path.dirname(DATA), os.pardir,
                                   "metrics", "step_mfu.py"), "mfu").read
    one, many = run(1), run(chips)
    assert readers.spmm_roofline(many) == pytest.approx(
        readers.spmm_roofline(one), rel=1e-12)
    assert mfu(many) == pytest.approx(mfu(one), rel=1e-12)
    assert readers.idle_share(many) == pytest.approx(readers.idle_share(one))


def test_recorded_host_trace_parses(tmp_path):
    """A trace recorded here (no device plane): the window annotation is
    found and the reducer reports no device time."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.wait"):
                f(x).block_until_ready()
    t = trace_reduce.load(str(tmp_path), 1)
    assert t.window_s > 0
    assert [sp.name for sp in t.host if sp.name == "bench.wait"] == [
        "bench.wait"]
    assert t.busy_s == 0.0


@pytest.mark.skipif(not os.path.exists(os.path.join(DATA, "tpu")),
                    reason="no recorded TPU trace")
def test_recorded_tpu_trace():
    """A short trace recorded on a TPU v5e: one jitted rowsplit SpMM call
    (256 x 512, 16 nonzeros a row, 128 columns) inside ``bench.window``
    and ``bench.wait`` annotations.  Its device clock runs about 1.5 ms
    behind the host's; after the move every device operation lies inside
    the wait."""
    t = trace_reduce.load(os.path.join(DATA, "tpu"), 1)
    assert sum(map(readers.is_spmm_kernel, t.devices[0])) == 1
    assert t.op_seconds(readers.is_spmm_kernel) == pytest.approx(
        27.53e-6, rel=1e-3)
    ops = t.devices[0]
    wait = next(sp for sp in t.host if sp.name == "bench.wait")
    assert wait.start <= ops[0].start and ops[-1].end <= wait.end
    assert 0 < t.busy_s <= t.window_s
    # The kernel's name is its HLO instruction, as the reader expects.
    assert any(o.name.startswith("%rowsplit_execute") for o in ops)


def test_clock_offset_bounds():
    # Device run 1 at 10..20 was enqueued at host 105 and completed at
    # 130: the offset lies in [95, 110]; run 2 narrows it to [100, 110].
    runs = {"1": (10, 20), "2": (50, 60)}
    enq = {"1": 105, "2": 150}
    done = {"1": 130, "2": 175}
    assert trace_reduce.clock_offset(runs, enq, done) == 105
