"""The readers of the program's own spans (``harness/spans.py``): on
synthetic host and device events with hand-worked answers, and on a
trace recorded here on the CPU while the real served stack runs a few
requests under the harness's ``Probe``."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import spans, trace  # noqa: E402
from harness.record import TraceWindow  # noqa: E402
from harness.spec import load_reader  # noqa: E402

T = ("/host:CPU", "serve")


def _sp(start, end, name, thread=T, **args):
    return spans.Span(start, end, name, args, thread)


def _op(start, dur):
    return trace.Op(start=start, dur=dur, name="fusion.1", run=None,
                    kernel=None, category="fusion")


def test_self_time_leaves_out_nested_children():
    evs = [_sp(0, 100, "fs.tick"),
           _sp(10, 40, "fs.step"), _sp(20, 30, "fs.launch"),
           _sp(35, 60, "fs.step"),      # overlaps the first: one union
           _sp(70, 80, "fs.rebind"),
           _sp(90, 120, "fs.step"),     # runs past the tick: not its own
           _sp(200, 250, "fs.tick"), _sp(210, 220, "fs.step",
                                         thread=("/host:CPU", "other")),
           _sp(300, 304, "fs.tick")]
    # tick 1: 100 - (10..60) - (70..80) = 40; tick 2: another thread's
    # step is not its child: 50; tick 3: 4
    assert spans.self_ns(evs, "fs.tick", ("fs.step", "fs.rebind")) == \
        [40, 50, 4]
    assert spans.median_self_ms(evs, "fs.tick", ("fs.step", "fs.rebind")) \
        == pytest.approx(40e-6)
    assert spans.median_self_ms(evs[:1] + evs[4:5], "fs.tick",
                                ("fs.rebind",)) == pytest.approx(90e-6)
    assert spans.median_self_ms(evs, "fs.pump", ()) is None


def test_idle_outside_ticks_with_partial_overlap():
    # device busy [0,25) [30,50) [60,70) over a window of 100 ns; its
    # span is (0, 70). Ticks [20,35) and [55,90): the second is clipped
    # to 70. Idle: [25,30) [50,60) in the span, and 30 ns after it.
    # Outside ticks: [50,55) = 5 ns in the span, plus the 30 after.
    d = trace.DeviceTrace("/device:TPU:0",
                          [_op(0, 25), _op(30, 20), _op(60, 10)])
    t = trace.Trace(devices=[d], host=[], span=(0, 70))
    evs = [_sp(20, 35, "fs.tick"), _sp(55, 90, "fs.tick"),
           _sp(0, 100, "fs.pump")]
    got = spans.idle_outside_share(t, evs, "fs.tick", 100e-9)
    assert got == pytest.approx(35.0)
    # at most device_idle_share (45% here), whatever the ticks
    assert spans.idle_outside_share(t, evs[:1], "fs.tick", 100e-9) \
        == pytest.approx(40.0)
    # two chips: the mean of what each covers
    d2 = trace.DeviceTrace("/device:TPU:1", [_op(0, 70)])
    t2 = trace.Trace(devices=[d, d2], host=[], span=(0, 70))
    assert spans.idle_outside_share(t2, evs, "fs.tick", 100e-9) \
        == pytest.approx(100 - (65 + 70) / 2)


def test_idle_gaps_are_put_down_to_the_innermost_span():
    # busy [10,20) [30,40) [45,90) over the span (0, 100): gaps [0,10)
    # mid 5, [20,30) mid 25, [40,45) mid 42, [90,100) mid 95
    d = trace.DeviceTrace("/device:TPU:0",
                          [_op(10, 10), _op(30, 10), _op(45, 45)])
    t = trace.Trace(devices=[d], host=[], span=(0, 100))
    assert spans.idle_gaps(d, (0, 100)) == [(0, 10), (20, 30), (40, 45),
                                            (90, 100)]
    assert spans.idle_gaps(d, (15, 42)) == [(20, 30), (40, 42)]
    evs = [_sp(0, 50, "fs.tick"), _sp(20, 30, "fs.step"),
           _sp(22, 28, "fs.launch"), _sp(41, 43, "fs.wait"),
           _sp(60, 99, "fs.http.write")]
    longest, by = spans.idle_by_span(t, evs, n=2)
    assert longest == [[10e-6, "fs.tick"], [10e-6, "fs.launch"]]
    assert by == pytest.approx({"fs.tick": 10e-6, "fs.launch": 10e-6,
                                "fs.wait": 5e-6, "fs.http.write": 10e-6})
    # no spans: every gap is "none"; two chips sum their gaps
    d2 = trace.DeviceTrace("/device:TPU:1", [_op(0, 100)])
    t2 = trace.Trace(devices=[d, d2], host=[], span=(0, 100))
    assert spans.idle_by_span(t2, [])[1] == pytest.approx({"none": 35e-6})


def test_a_window_without_program_spans_reads_none(tmp_path, monkeypatch):
    from harness.record import RunRecord
    d = trace.DeviceTrace("/device:TPU:0", [_op(0, 25)])
    rec = RunRecord(cell="c", window_s=1.0, setup_s=1.0, records=[],
                    dims=None, peaks=None, chips=1)
    readers = [load_reader(n) for n in ("sched_host_ms_per_tick",
                                        "stage_host_ms_per_step",
                                        "idle_outside_tick_share")]
    assert [r(rec) for r in readers] == [None] * 3       # not traced
    rec.traced = TraceWindow(trace.Trace([d], [], (0, 25)), 0.0, 1e-7)
    monkeypatch.chdir(tmp_path)
    assert [r(rec) for r in readers] == [None] * 3       # no trace file
    assert spans.idle_outside_share(rec.traced.trace, [], "fs.tick",
                                    1e-7) is None
    assert spans.launches([]) == []


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A tiny served stack (``tiny_run``'s configuration, the launcher's
    real engine) runs requests with the harness's probe recording while
    the profiler traces; returns (xplane path, probe)."""
    import jax
    import tiny_run
    from harness import system
    from harness.spec import Cell
    from repro.core.task_pool import Request
    c = Cell(name="tiny.chat", config="tiny", traffic="chat", chips=1,
             why="test", config_data=tiny_run.CONFIG,
             traffic_data=tiny_run.TRAFFIC["chat"])
    door, _loop, probe = system.build(c, 2**31 + 5)
    reqs = [Request(req_id=f"r{i}", arrival=0.01 * i, prompt_len=p,
                    output_len=o)
            for i, (p, o) in enumerate(((40, 6), (300, 4), (90, 8)))]
    for r in reqs:
        door.submit(r, "standard")
    out = tmp_path_factory.mktemp("trace")
    probe.recording = True
    jax.profiler.start_trace(str(out))
    door.run()
    jax.profiler.stop_trace()
    assert all(r.state == "done" for r in door.requests.values())
    return trace.find_xplane(str(out)), probe


def test_launches_from_the_step_spans_equal_the_probes(served):
    path, probe = served
    got = spans.launches(spans.load(path))
    key = lambda l: (l.phase, l.island, l.decode, l.prefill)  # noqa: E731
    assert len(got) == len(probe.launches) > 0
    assert [key(l) for l in got] == [key(l) for l in probe.launches]
    assert {l.phase for l in got} >= {"mixed", "decode"}


def test_readers_on_a_recorded_cpu_trace(served):
    path, _ = served
    evs = spans.load(path)
    assert len({s.thread for s in evs}) == 1
    assert spans.median_self_ms(evs, "fs.tick",
                                ("fs.step", "fs.rebind")) > 0
    assert spans.median_self_ms(evs, "fs.step",
                                ("fs.wait", "fs.harvest")) > 0
    # trace.load keeps the harness's own spans only, and the CPU has
    # no TPU planes: host, top_ops and idle_gaps are what they were
    t = trace.load(path)
    assert t.host and all(n.startswith("bench.") for _, _, n in t.host)
    assert {n for _, _, n in t.host} >= {"bench.tick", "bench.decode"}
    assert t.devices == [] and trace.top_ops(t) == []
    assert trace.idle_gaps(t, t.span) == []


def test_span_gaps_tool_on_a_recorded_cpu_trace(served):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    import span_gaps
    path, _ = served
    out = span_gaps.analyse(str(Path(path).parent))
    assert out["chips"] == 0 and out["idle_ms"] == 0
    assert out["spans_ms"]["fs.tick"]["n"] > 0
    assert out["spans_ms"]["fs.step"]["n"] > 0
    assert out["tick_self_ms"] > 0 and out["runs_by_module"] == {}
