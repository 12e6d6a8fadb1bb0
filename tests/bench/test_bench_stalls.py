"""The stall lines of a run's log: the longest engine step in the window with
its longest phase, and the garbage collections that ran in the window."""

import dataclasses
import gc

import bench_testlib  # noqa: F401  (puts bench/ on the path)
from harness import cell


@dataclasses.dataclass
class Rec:
    t0: float
    t1: float
    phases: dict


def test_longest_step_names_the_stall_and_its_phase():
    recs = [Rec(10.0 + 0.05 * i, 10.048 + 0.05 * i, {"device_wait": 0.03, "sample": 0.002})
            for i in range(20)]
    recs.append(Rec(11.2, 12.8, {"device_wait": 1.55, "sample": 0.002}))
    recs.append(Rec(9.0, 9.9, {"device_wait": 0.9}))  # before the window
    line = cell.longest_step(recs, 10.0, 13.0)
    assert "median 48.00 ms" in line
    assert "longest 1600.00 ms (device_wait 1550.00 ms, 1.2 s into the window)" in line
    assert line.endswith("1 over 250 ms")
    assert cell.longest_step(recs, 20.0, 30.0) == "no engine step in the window"


def test_gc_clock_counts_full_collections_in_the_window():
    clock = cell.GcClock()
    try:
        gc.collect()
        gc.collect(0)
        t0, t1 = clock.events[0][0] - 1.0, clock.events[-1][0] + 1.0
        line = clock.report(t0, t1)
        assert line.startswith("gc in the window: ")
        assert "1 of generation 2" in line
        assert clock.report(t1 + 1.0, t1 + 2.0).startswith(
            "gc in the window: 0 collections, 0 of generation 2")
    finally:
        gc.callbacks.remove(clock)
