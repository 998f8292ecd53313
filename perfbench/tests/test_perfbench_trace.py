"""The reduction from a profiler trace to device metrics, on synthetic
device events with hand-worked answers, and on a small trace recorded
here on the CPU (host spans only)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import device, trace, work  # noqa: E402
from harness.peaks import V5E  # noqa: E402
from harness.record import RunRecord, TraceWindow  # noqa: E402
from harness.system import Launch  # noqa: E402

# operation names as a v5e trace gives them: the HLO instruction's text
DECODE_HLO = ("%closed_call.23 = (f32[16,1,2048]{2,1,0:T(1,128)S(1)}, "
              "f32[16,1,32]{2,1,0:T(1,128)}) custom-call(s32[16,128] %a, "
              "bf16[3826,16,2048] %k), custom_call_target=\"tpu_custom_call\"")
PREFILL_HLO = ("%closed_call.45 = (bf16[16,32,256,64]{3,2,1,0}, "
               "f32[16,32,256,1]{3,2,1,0}) custom-call(s32[16,128] %a), "
               "custom_call_target=\"tpu_custom_call\"")
APPEND_HLO = ("%closed_call.22 = (bf16[3826,16,2048]{2,1,0}, "
              "bf16[3826,16,2048]{2,1,0}) custom-call(s32[16] %a), "
              "custom_call_target=\"tpu_custom_call\"")


def test_kernels_are_told_apart_by_their_results():
    assert trace.kernel_of(DECODE_HLO) == "paged_decode"
    assert trace.kernel_of(PREFILL_HLO) == "flash_prefill"
    assert trace.kernel_of(APPEND_HLO) == "append"
    assert trace.kernel_of("%fusion.7 = f32[4] fusion(%x)") is None
    assert trace._category("%dynamic-slice_bitcast_fusion.5 = bf16[3] "
                           "fusion(%a)") == "dynamic-slice_bitcast_fusion"
    assert trace._category("%dynamic-slice_bitcast_fusion.9.remat = bf16[3]"
                           " fusion(%a)") == "dynamic-slice_bitcast_fusion"


def test_operations_join_the_run_that_holds_them():
    ops = [_op(5, 1), _op(12, 1), _op(30, 1)]
    trace._assign_runs(ops, [(10, 20, 7), (0, 10, 6)])
    assert [o.run for o in ops] == [6, 7, None]


def _op(start, dur, run=None, kernel=None, name="fusion.1"):
    return trace.Op(start=start, dur=dur, name=name, run=run, kernel=kernel,
                    category=trace._category(name))


def _two_runs():
    # run 1 (decode): a layer loop [0,25) holding ops at [0,10) and
    # [15,25) -> 25 ns busy (the loop's own control counts)
    # run 2 (mixed): ops at [30,40), [35,50), [60,70) -> 30 ns busy
    d = trace.DeviceTrace("/device:TPU:0", [
        _op(0, 25, 1, None, "%while.5 = (s32[]) while(%t)"),
        _op(0, 10, 1, "paged_decode", "step.1"), _op(15, 10, 1),
        _op(30, 10, 2, "paged_decode", "step.1"),
        _op(35, 15, 2, "flash_prefill", "step.2"),
        _op(60, 10, 2, None, "copy.3")])
    return d


def test_busy_union_runs_and_kernel_time():
    d = _two_runs()
    assert d.busy_intervals() == [(0, 25), (30, 50), (60, 70)]
    assert d.busy_ns() == 55
    assert trace.runs_by_phase(d) == {1: "decode", 2: "mixed"}
    assert trace.run_time_ns(d) == {1: 25, 2: 30}
    assert trace.kernel_ns(d, "paged_decode") == 20
    assert trace.kernel_ns(d, "flash_prefill") == 15


def test_top_ops_and_idle_gaps_by_host_span():
    d = _two_runs()
    t = trace.Trace(devices=[d], host=[(0, 100, "bench.tick"),
                                       (52, 58, "bench.decode")],
                    span=(0, 70))
    ops = dict((k, v) for k, v in trace.top_ops(t))
    assert "while" not in ops          # a loop holds its body's time
    assert ops["paged_decode"] == pytest.approx(20e-9)
    assert ops["copy"] == pytest.approx(10e-9)
    gaps = trace.idle_gaps(t, (0, 80))
    # gaps: [25,30) 5, [50,60) 10 (mid 55: bench.decode), [70,80) 10
    # (mid 75: bench.tick)
    assert gaps[0][1] == pytest.approx(10e-9)
    assert {g[0] for g in gaps[:2]} == {"bench.decode", "bench.tick"}


def _record(launches, devices, seconds=1e-7):
    dims = work.Dims(layers=2, d_model=64, heads=4, kv_heads=2,
                     head_dim=16, d_ff=128, vocab=256)
    rec = RunRecord(cell="t", window_s=1.0, setup_s=1.0, records=[],
                    dims=dims, peaks=V5E, chips=len(devices),
                    launches=launches)
    rec.traced = TraceWindow(trace.Trace(devices, [], (0, 70)), 0.0,
                             seconds)
    return rec


def test_mfu_and_roofline_from_launches_and_trace():
    l1 = Launch("decode", 0.0, 0.0, (0, 1, 1), decode=[(0, 100), (0, 20)])
    l2 = Launch("mixed", 0.0, 0.0, (0, 1, 1), decode=[(0, 50)],
                prefill=[(0, 0, 32, True)])
    rec = _record([l1, l2], [_two_runs()])
    m = rec.dims
    flops = sum(work.decode_row_flops(m, c) for c in (100, 20, 50))
    # decode and mixed runs: 55 ns of device time
    assert device.mfu(rec, "decode") == pytest.approx(
        100 * flops / (55e-9 * V5E.flops_bf16))
    pf = work.prefill_chunk_flops(m, 0, 32, True)
    assert device.mfu(rec, "prefill") == pytest.approx(
        100 * pf / (30e-9 * V5E.flops_bf16))
    least = 0.0
    for rows in ([100, 20], [50]):
        fb = [work.decode_kernel_work(m, c) for c in rows]
        least += m.layers * work.roofline_s(
            sum(x[0] for x in fb), sum(x[1] for x in fb), V5E.flops_bf16,
            V5E.hbm_bw)
    assert device.kernel_roofline(rec, "paged_decode") == pytest.approx(
        100 * least / 20e-9)
    assert device.busy_s(rec) == pytest.approx(55e-9)
    assert device.idle_share(rec) == pytest.approx(45.0)


def test_nothing_to_read_gives_none():
    rec = _record([], [trace.DeviceTrace("/device:TPU:0", [])])
    assert device.mfu(rec, "decode") is None
    assert device.kernel_roofline(rec, "paged_decode") is None
    rec.traced = None
    assert device.idle_share(rec) is None


def test_tp_island_splits_each_group_over_its_chips():
    # one TP2 group: both chips run half the heads of the same rows
    l1 = Launch("decode", 0.0, 0.0, (0, 2, 2), decode=[(0, 64)])
    d0 = trace.DeviceTrace("/device:TPU:0",
                           [_op(0, 10, 1, "paged_decode", "step.1")])
    d1 = trace.DeviceTrace("/device:TPU:1",
                           [_op(0, 10, 1, "paged_decode", "step.1")])
    rec = _record([l1], [d0, d1])
    m = rec.dims
    f, b = work.decode_kernel_work(m, 64)
    least = 2 * m.layers * work.roofline_s(f / 2, b / 2, V5E.flops_bf16,
                                           V5E.hbm_bw)
    assert device.kernel_roofline(rec, "paged_decode") == pytest.approx(
        100 * least / 20e-9)


def test_a_recorded_cpu_trace_yields_the_bench_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.tick"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = trace.load(trace.find_xplane(str(tmp_path)))
    assert any(name == "bench.tick" for _, _, name in t.host)
    assert t.devices == []          # the CPU has no TPU planes


def test_output_tokens_are_counted_where_the_launches_make_them():
    from harness.spec import load_reader
    dec = Launch("decode", 0.0, 0.0, (0, 1, 1), decode=[(0, 10), (0, 20)])
    mix = Launch("mixed", 0.0, 0.0, (0, 1, 1), decode=[(0, 30)],
                 prefill=[(0, 0, 256, False), (0, 256, 100, True)])
    pre = Launch("prefill", 0.0, 0.0, (0, 1, 1),
                 prefill=[(0, 0, 256, False)])
    assert [l.tokens for l in (dec, mix, pre)] == [2, 2, 0]
    read = load_reader("output_tok_s")
    rec = _record([dec, mix, pre], [0])
    assert read(rec) is None          # no clock for the made tokens
    rec.gen_s = 0.5
    assert read(rec) == pytest.approx(8.0)
    rec.launches = []
    assert read(rec) is None
