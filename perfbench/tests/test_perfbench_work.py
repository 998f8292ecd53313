"""Operation and byte counts of the benchmark, against hand-worked
shapes."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import work  # noqa: E402
from harness.peaks import V5E, peaks_for  # noqa: E402
from harness.stats import percentile, spread  # noqa: E402

STABLELM = work.Dims(layers=24, d_model=2048, heads=32, kv_heads=32,
                     head_dim=64, d_ff=5632, vocab=100352)
QWEN = work.Dims(layers=36, d_model=2560, heads=32, kv_heads=8,
                 head_dim=128, d_ff=9728, vocab=151936)


def test_layer_params_by_hand():
    # q, k, v, o of 2048 x 2048 each, and three 2048 x 5632 MLP matrices
    assert STABLELM.layer_matmul_params == 4 * 2048 * 2048 + 3 * 2048 * 5632
    # q 2560 x 4096, k and v 2560 x 1024, o 4096 x 2560, MLP 3 x 2560 x 9728
    assert QWEN.layer_matmul_params == (2560 * 4096 + 2 * 2560 * 1024
                                        + 4096 * 2560 + 3 * 2560 * 9728)


def test_token_flops_match_parameter_count():
    # 2 FLOPs per weight per token: 24 layers of 51.38M weights
    assert work.token_matmul_flops(STABLELM) == 2 * 24 * 51_380_224
    assert work.head_flops(STABLELM) == 2 * 2048 * 100352


@pytest.mark.parametrize("prior,chunk,want", [
    (0, 1, 1), (0, 4, 1 + 2 + 3 + 4), (10, 3, 11 + 12 + 13),
    (1000, 256, 256 * 1000 + 256 * 257 / 2)])
def test_causal_context_sum(prior, chunk, want):
    assert work.causal_ctx_sum(prior, chunk) == want


def test_decode_kernel_work_by_hand():
    # one row at 100 tokens: 7 pages of 16; K and V, 32 heads of 64, bf16
    f, b = work.decode_kernel_work(STABLELM, 100)
    assert f == 4 * 32 * 64 * 100
    assert b == 2 * 7 * 16 * 32 * 64 * 2 + 2 * 32 * 64 * 2
    # GQA reads 8 KV heads of 128 for 32 query heads
    f, b = work.decode_kernel_work(QWEN, 16)
    assert f == 4 * 32 * 128 * 16
    assert b == 2 * 1 * 16 * 8 * 128 * 2 + 2 * 32 * 128 * 2


def test_prefill_kernel_work_by_hand():
    f, b = work.prefill_kernel_work(STABLELM, 256, 256)
    assert f == 4 * 32 * 64 * (256 * 256 + 256 * 257 / 2)
    assert b == 2 * 32 * 16 * 32 * 64 * 2 + 2 * 256 * 32 * 64 * 2


def test_decode_row_flops_adds_head_and_attention():
    ctx = 1000
    want = (work.token_matmul_flops(STABLELM) + 2 * 2048 * 100352
            + 4 * 24 * 32 * 64 * ctx)
    assert work.decode_row_flops(STABLELM, ctx) == want
    pre = work.prefill_chunk_flops(STABLELM, 0, 2, final=True)
    assert pre == (2 * work.token_matmul_flops(STABLELM)
                   + work.attn_flops(STABLELM, 3)
                   + work.head_flops(STABLELM))


def test_roofline_takes_the_larger_bound():
    # 197 TFLOP at 197 TFLOP/s is 1 s; 819 GB at 819 GB/s is 1 s
    assert work.roofline_s(197e12, 1.0, V5E.flops_bf16, V5E.hbm_bw) == 1.0
    assert work.roofline_s(1.0, 2 * 819e9, V5E.flops_bf16,
                           V5E.hbm_bw) == 2.0


def test_peaks_table_refuses_unknown_chips():
    assert peaks_for("TPU v5 lite") is V5E
    with pytest.raises(KeyError):
        peaks_for("TPU v99")


def test_percentile_and_spread():
    v = list(range(1, 11))                      # 1..10
    assert percentile(v, 50) == 5.5
    assert percentile(v, 90) == pytest.approx(9.1)
    assert percentile([], 90) is None
    # quartiles of 1..8 by the default (exclusive) method: 2.25, 4.5, 6.75
    assert spread(range(1, 9)) == pytest.approx((6.75 - 2.25) / 4.5)
