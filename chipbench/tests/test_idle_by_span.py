"""The program's ``gr:`` spans as ``program_spans.load`` reads them, and the
reductions of ``tools/idle_by_span.py``: on hand-made events, and on a trace
recorded on a TPU v5e with ``tools/record_trace.py`` (3 runs of B&S at 4
stocks x 2 iterations, ``bs_small_gr.xplane.pb``)."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import bench
from chipbench import program_spans as P
from chipbench import trace as T

E = T.Event
TPU = "/device:TPU:0"
RECORDED = Path(__file__).with_name("data") / "bs_small_gr.xplane.pb"
RUNS, STOCKS, ITERS = 3, 4, 2


def gr(name, start, dur, line="main", **stats):
    return P.ProgramSpan(name, start, dur, line, tuple(stats.items()))


def hand_made():
    """A window [0, 1000] ns: two kernels on the device; on the main thread
    two launches and one host read (wait, then copy); on a lane two uploads
    (dispatch, then block) and a kernel."""
    ops = [E("k", 300, 50), E("k", 800, 50)]
    spans = sorted([
        gr("launch", 10, 40, uid=2), gr("schedule", 20, 10, uid=1),
        gr("launch", 100, 60, uid=4),
        gr("h2d", 50, 200, "lane", uid=1, nbytes=64),
        gr("dispatch", 50, 20, "lane"), gr("block", 70, 180, "lane"),
        gr("kernel", 290, 70, "lane", uid=2),
        gr("h2d", 400, 100, "lane", uid=3, nbytes=64),
        gr("host_read", 600, 350), gr("host_wait", 600, 250, uid=5),
        gr("d2h", 860, 80, nbytes=64),
        gr("launch", 990, 30, uid=9),           # ends after the window
    ], key=lambda s: s.start_ns)
    trace = T.Trace(devices={TPU: {T.OPS: ops}},
                    spans=[E("cb:window", 0, 1000), E("cb:read", 590, 400)])
    return ops, trace, spans


def test_span_table_counts_and_means_whole_spans_in_the_window():
    _, _, spans = hand_made()
    table = P.span_table(spans, 0, 1000)
    assert table["launch"] == (2, pytest.approx(50e-3))
    assert table["h2d"] == (2, pytest.approx(150e-3))
    assert table["d2h"] == (1, pytest.approx(80e-3))


def test_idle_in_copy_is_the_idle_time_under_any_copy():
    ops, _, spans = hand_made()
    # Copies cover [50, 250], [400, 500] and [860, 940]; the device is idle
    # outside [300, 350] and [800, 850]: all 380 ns of copies are idle.
    assert P.idle_in_copy_ns(ops, spans, 0, 1000) == pytest.approx(380)
    assert P.idle_in_copy_ns(ops, spans, 0, 200) == pytest.approx(150)


def test_idle_by_span_splits_a_gap_at_every_span_edge():
    ops, trace, spans = hand_made()
    by = P.idle_by_span(ops, trace, spans, 0, 1000)
    assert sum(by.values()) == pytest.approx(900e-9)
    # Under the benchmark's read: the lane idle, the host waiting, then the
    # copy to the host, then the benchmark's own work after the read.
    assert by[("read", "host_read>host_wait")] == pytest.approx(200e-9)
    assert by[("read", "host_read>d2h")] == pytest.approx(80e-9)
    # 10 ns before the host read opens, 40 ns after it closes.
    assert by[("read", "-")] == pytest.approx(50e-9)
    # Device work on a lane is preferred over a wait on the main thread.
    assert by[("no benchmark span", "h2d>block")] == pytest.approx(180e-9)
    assert by[("no benchmark span", "h2d")] == pytest.approx(100e-9)


def test_program_label_prefers_device_work_then_the_innermost():
    w = gr("host_wait", 0, 100)
    r = gr("host_read", 0, 120)
    b = gr("block", 10, 20, "lane")
    h = gr("h2d", 0, 50, "lane")
    assert P.program_label([r, w, h, b]) == "h2d>block"
    assert P.program_label([r, w]) == "host_read>host_wait"
    assert P.program_label([r, gr("lock", 0, 5)]) == "host_read>lock"
    assert P.program_label([]) == "-"


def test_a_runtime_without_spans_gives_none(monkeypatch):
    monkeypatch.setattr(P, "program_prefix", lambda: None)
    assert P.load(str(RECORDED)) == []
    assert P.load(str(RECORDED.with_name("bs_small.xplane.pb"))) == []


def test_the_benchmark_reads_only_its_own_spans():
    """The benchmark's loader and its metrics see the same kind of trace
    with the program's spans in it: only ``cb:`` spans, ``cb:`` labels."""
    trace = T.load(str(RECORDED))
    assert trace.spans and all(s.name.startswith(T.PREFIX)
                               for s in trace.spans)
    run = object.__new__(bench.Run)
    run.trace, run.window_ns = trace, trace.window()
    run.devices = [SimpleNamespace(id=0)]
    labels = {k for k, _ in run.breakdown()["idle_gaps"]}
    assert labels <= {"read", "prep", "run", "launch:BS", "gc",
                      "no benchmark span"}


def test_recorded_chip_trace_with_program_spans():
    trace = T.load(str(RECORDED))
    spans = P.load(str(RECORDED))
    t0, t1 = trace.window()
    per_run = RUNS * STOCKS * ITERS
    table = P.span_table(spans, t0, t1)
    for name in ("h2d", "d2h", "launch", "kernel", "host_read"):
        assert table[name][0] == per_run, name
    uploads = [s for s in spans if s.name == "h2d"]
    assert all(dict(s.stats)["nbytes"] == 4096 * 4 for s in uploads)
    # The program's launch span lies inside the benchmark's.
    launches = T.spans_in(trace, "launch:", t0, t1)
    assert table["launch"][1] <= sum(s.dur_ns for s in launches) / len(
        launches) * 1e-3
    ops = trace.ops(TPU)
    idle = t1 - t0 - T.busy_ns(ops, t0, t1)
    assert 0 < P.idle_in_copy_ns(ops, spans, t0, t1) <= idle
    lanes = {s.line for s in spans if s.name in ("h2d", "kernel")}
    mains = {s.line for s in spans if s.name == "launch"}
    assert len(mains) == 1 and not lanes & mains
    by = P.idle_by_span(ops, trace, spans, t0, t1)
    assert sum(by.values()) == pytest.approx(idle * 1e-9)
    assert any(g == "host_read>d2h" for _, g in by)
