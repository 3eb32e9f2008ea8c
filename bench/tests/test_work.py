"""The roofline and utilization arithmetic against hand-computed shapes."""
from bench import work

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_spmm_call_counts_granite_w1():
    # w1 of granite-3-2b: 8192 x 2048, 512 kept a row, 128 bf16 columns.
    c = work.SpmmCall(m=8192, k=2048, nnz=8192 * 512, n=128, val_bytes=4,
                      b_bytes=2, c_bytes=2)
    assert c.flops == 2 * 4194304 * 128 == 1073741824
    # values + column indices, row pointers, B once, C once
    want = 4194304 * 8 + 8193 * 4 + 2048 * 128 * 2 + 8192 * 128 * 2
    assert c.compulsory_bytes == want == 36208644
    # bytes bound: 36208644 / 819e9 s
    assert abs(work.least_seconds(c.flops, c.compulsory_bytes, PEAK)
               - 36208644 / 819e9) < 1e-15


def test_spmm_call_counts_graph():
    # graph500-s16: 65,536 vertices, 1,819,722 nonzeros, 128 features.
    c = work.SpmmCall(m=65536, k=65536, nnz=1819722, n=128, val_bytes=4,
                      b_bytes=4, c_bytes=4)
    want = 1819722 * 8 + 65537 * 4 + 2 * 65536 * 128 * 4
    assert c.compulsory_bytes == want == 81928788
    assert work.spmm_least_seconds([c, c], PEAK) == 2 * want / 819e9


def test_flop_bound_wins_when_dense_enough():
    # 1e12 flops over 1 byte: the compute bound sets the least time.
    assert work.least_seconds(1e12, 1, PEAK) == 1e12 / 197e12


def test_lm_flops_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 1, "num_hidden_layers": 3,
           "vocab_size": 10}
    # head dim 4: q 8, kv 4; projections 8*8 + 2*8*4 + 8*8 = 192
    # per token: 2*(192 + nnz 50)*3 layers + 2*8*10 = 1452 + 160 = 1612
    # attention for L=3: 4*8*(3*4/2)*3 layers = 576
    assert work.lm_flops(cfg, 50, 3) == 1612 * 3 + 576


def test_step_mfu_reader():
    import dataclasses

    from bench.harness import load_module
    import os
    read = load_module(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "metrics", "step_mfu.py"), "m").read

    @dataclasses.dataclass
    class W:
        flops: float
        seconds: float

    @dataclasses.dataclass
    class R:
        window: W
        peak: dict

    # 1.97e12 flops in 1 s on a 197e12 chip: 1%.
    assert abs(read(R(W(1.97e12, 1.0), PEAK)) - 1.0) < 1e-12
