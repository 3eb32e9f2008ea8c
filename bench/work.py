"""Operations and compulsory bytes of the work a cell asks for.

Kept with the benchmark so that every PR computes a kernel's roofline
share and a step's utilization the same way.  Everything here is
arithmetic on shapes; nothing reads the program.
"""
from __future__ import annotations

import dataclasses

INDEX_BYTES = 4          # int32 column indices and row pointers


@dataclasses.dataclass(frozen=True)
class SpmmCall:
    """One sparse-times-dense product ``C (m, n) = A (m, k) @ B (k, n)``
    with ``nnz`` stored nonzeros.  Byte widths are those of the operands
    as the caller holds them: values as stored, B as passed in, C as
    handed back."""

    m: int
    k: int
    nnz: int
    n: int
    val_bytes: int
    b_bytes: int
    c_bytes: int

    @property
    def flops(self) -> int:
        """One multiply and one add per nonzero and column of B."""
        return 2 * self.nnz * self.n

    @property
    def compulsory_bytes(self) -> int:
        """CSR values, column indices and row pointers read once, B read
        once and C written once."""
        return (self.nnz * (self.val_bytes + INDEX_BYTES)
                + (self.m + 1) * INDEX_BYTES
                + self.k * self.n * self.b_bytes
                + self.m * self.n * self.c_bytes)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the operations
    over the bf16 peak and the bytes over the HBM bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def spmm_least_seconds(calls, peak: dict) -> float:
    """Sum of each call's least time (calls run one after another)."""
    return sum(least_seconds(c.flops, c.compulsory_bytes, peak)
               for c in calls)


def lm_flops(cfg: dict, nnz_per_layer: int, length: int) -> int:
    """Operations one sequence of ``length`` tokens requires through a
    decoder whose MLP matrices keep ``nnz_per_layer`` nonzeros in all:
    dense attention projections, causal attention (each query against
    itself and the keys before it), the kept MLP nonzeros and the tied
    unembedding.  Norms, RoPE and softmax are left out."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    proj = d * q + 2 * d * kv + q * d
    per_token = 2 * (proj + nnz_per_layer) * cfg["num_hidden_layers"] \
        + 2 * d * cfg["vocab_size"]
    # QK^T and PV: 2 * 2 * q flops per (query, key) pair, L(L+1)/2 pairs.
    attn = 4 * q * (length * (length + 1) // 2) * cfg["num_hidden_layers"]
    return per_token * length + attn
