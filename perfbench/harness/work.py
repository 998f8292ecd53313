"""Operations and bytes of the served model and its attention kernels,
computed from shapes.

Only useful work counts: the rows of a step that carry a live request,
the context each row attends over, and the pages that context fills.
Padding rows, padded block-table columns and re-reads are the kernel's
cost, not work, so a kernel that wastes them reads below its roofline.
Weights and caches are bf16 (2 bytes).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

BYTES = 2           # bf16
PAGE = 16           # tokens per KV block (the program's BLOCK_TOKENS)


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, c: Dict) -> "Dims":
        hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=hd,
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"])

    @property
    def layer_matmul_params(self) -> int:
        d, h, kv, hd = self.d_model, self.heads, self.kv_heads, self.head_dim
        return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * self.d_ff


def token_matmul_flops(m: Dims) -> float:
    """Multiply-adds x 2 of every layer's projections for one token."""
    return 2.0 * m.layers * m.layer_matmul_params


def head_flops(m: Dims) -> float:
    """The vocabulary projection of one sampled position."""
    return 2.0 * m.d_model * m.vocab


def attn_flops(m: Dims, ctx_sum: float) -> float:
    """QK^T and PV over ``ctx_sum`` (query, key) pairs, all layers."""
    return 4.0 * m.layers * m.heads * m.head_dim * ctx_sum


def causal_ctx_sum(prior: int, chunk: int) -> float:
    """Sum of context lengths of ``chunk`` queries after ``prior``
    cached tokens, each attending to itself and everything before."""
    return chunk * prior + chunk * (chunk + 1) / 2.0


def decode_row_flops(m: Dims, ctx: int) -> float:
    """Model FLOPs of one decode row whose context (its new token
    included) is ``ctx``."""
    return token_matmul_flops(m) + head_flops(m) + attn_flops(m, ctx)


def prefill_chunk_flops(m: Dims, prior: int, chunk: int,
                        final: bool) -> float:
    """Model FLOPs of one prompt chunk: its tokens' projections, their
    attention over the prior context and each other, and the one
    sampled position of a final chunk."""
    return (chunk * token_matmul_flops(m)
            + attn_flops(m, causal_ctx_sum(prior, chunk))
            + (head_flops(m) if final else 0.0))


def pages(tokens: int) -> int:
    return -(-int(tokens) // PAGE)


def decode_kernel_work(m: Dims, ctx: int) -> tuple:
    """(FLOPs, bytes) of the paged decode kernel for one row in one
    layer: scores and values over ``ctx`` tokens, the K and V pages that
    hold them, the query in and the output out."""
    flops = 4.0 * m.heads * m.head_dim * ctx
    kv = 2.0 * pages(ctx) * PAGE * m.kv_heads * m.head_dim * BYTES
    qo = 2.0 * m.heads * m.head_dim * BYTES
    return flops, kv + qo


def prefill_kernel_work(m: Dims, prior: int, chunk: int) -> tuple:
    """(FLOPs, bytes) of the paged flash-prefill kernel for one chunk in
    one layer: causal attention of ``chunk`` queries over ``prior`` +
    themselves, every K and V page read once, queries in and outputs
    out."""
    flops = 4.0 * m.heads * m.head_dim * causal_ctx_sum(prior, chunk)
    kv = 2.0 * pages(prior + chunk) * PAGE * m.kv_heads * m.head_dim * BYTES
    qo = 2.0 * chunk * m.heads * m.head_dim * BYTES
    return flops, kv + qo


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bw)
