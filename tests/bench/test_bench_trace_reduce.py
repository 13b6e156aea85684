"""The reduction from a profiler trace to busy time, top operations and
idle gaps: on hand-made planes, and on a trace recorded once on a TPU v5e
by ``record_trace_fixture.py`` (four 20 ms ``prepare`` host spans, each
followed by one jitted matmul step, inside the window span)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce as tr

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_four_steps.xplane.pb"


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=a, duration_ns=d) for n, a, d in events])


def _planes():
    host = NS(name="/host:CPU", lines=[
        _line("other thread", [("busy elsewhere", 0, 10_000)]),
        _line("python", [("bench_window", 100, 1000), ("prepare", 100, 300),
                         ("dispatch", 400, 50), ("prepare", 600, 300)]),
    ])
    dev = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [("jit_step", 0, 5000)]),
        _line("XLA Ops", [
            ("%fusion.1 = f32[8]{0:T(128)} fusion(%x)", 50, 100),
            ("%while.3 = (s32[], f32[8]{0}) while(%t)", 400, 300),   # holds the next two
            ("%dot.2 = f32[8,8]{1,0:T(8,128)} dot(%a, %b)", 420, 150),
            ("%fusion.1 = f32[8]{0:T(128)} fusion(%x)", 580, 100),
            ("%dot.2 = f32[8,8]{1,0:T(8,128)} dot(%a, %b)", 1050, 200)]),
    ])
    return [NS(name="/host:metadata", lines=[]), host, dev]


def test_interval_helpers():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]
    spans = [(0, 10, "outer"), (2, 4, "inner"), (1, 3, "early")]
    assert tr.innermost(spans, [0.5, 2.5, 3.5, 9, 11]) == [
        "outer", "early", "inner", "outer", tr.NO_SPAN]


def test_busy_top_ops_and_gap_attribution():
    s = tr.reduce_planes(_planes())
    # window [100, 1100]: busy [100,150] + [400,700] + [1050,1100] = 400 ns
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(400e-9)
    assert s.idle_share == pytest.approx(0.6)
    assert s.devices == 1
    # the while loop holds other ops and is not itself counted
    assert s.device_ops == [("%dot.2 = f32[8,8]", pytest.approx(200e-9)),
                            ("%fusion.1 = f32[8]", pytest.approx(150e-9))]
    # gaps [150,400] (middle in the first prepare) and [700,1050] (in the second)
    assert s.idle_gaps == [("prepare", pytest.approx(600e-9))]


def test_recorded_v5e_trace():
    s = tr.reduce_file(str(FIXTURE))
    # four 2048x2048 bf16 matmul steps of about 91 us inside an 86 ms window
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.08609099, rel=1e-6)
    assert s.busy_s == pytest.approx(0.000364154, rel=1e-6)
    assert s.device_ops[0] == ("%fusion = bf16[2048,2048]", pytest.approx(0.00036409, rel=1e-6))
    # every gap's middle lies in the 20 ms sleep inside a ``prepare`` span
    assert s.idle_gaps == [("$time sleep", pytest.approx(s.window_s - s.busy_s, rel=1e-9))]


def test_missing_window_or_device_is_an_error():
    planes = _planes()
    with pytest.raises(ValueError, match="no host span"):
        tr.reduce_planes([planes[2]])
    with pytest.raises(ValueError, match="no device ops"):
        tr.reduce_planes(planes[:2])
