"""``scripts/cycle_gap_join.py``'s arithmetic on a profile made by hand:
decode executions numbered by count from the host's ``cycle`` stats with
the two clocks 1.5 ms apart, a gap put down to the cycle whose interval
issued the late dispatch, and the fallback to the spans' own stats where
the slow-cycle log does not hold that cycle."""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = importlib.util.spec_from_file_location(
    "cycle_gap_join", os.path.join(HERE, "..", "scripts", "cycle_gap_join.py"))
join = importlib.util.module_from_spec(SPEC)
SPEC.loader.exec_module(join)

MS = 1_000_000  # nanoseconds
SKEW = 1.5  # the host's line runs ahead of the device's, ms


def _profile():
    """Decode executions of 10 ms, cycles 101-105, a prefill between two
    of them, and one 3 ms gap: the walk of block 102 took 17.7 ms, so
    dispatch 104 was issued after execution 103 had ended."""
    device = [("jit_decode", 0.0, 10.0), ("jit_decode", 10.05, 20.05),
              ("jit_prefill", 20.1, 25.1), ("jit_decode", 25.15, 35.15),
              ("jit_decode", 38.15, 48.15), ("jit_decode", 48.2, 58.2)]
    host = [("serve/dispatch", 2.0, 2.2, 102),
            ("serve/decode_args", 11.9, 12.0, 103),
            ("serve/dispatch", 12.05, 12.3, 103),
            ("serve/wait", 12.3, 20.05, 102),
            ("serve/harvest", 20.1, 37.8, 102),
            ("serve/decode_args", 37.85, 37.95, 104),
            ("serve/dispatch", 38.0, 38.1, 104),
            ("serve/wait", 38.1, 38.11, 103),
            ("serve/harvest", 38.2, 38.4, 103),
            ("serve/decode_args", 40.0, 40.05, 105),
            ("serve/dispatch", 40.1, 40.3, 105),
            ("serve/wait", 40.3, 48.15, 104),
            ("serve/dispatch", 50.2, 50.4, 106)]
    # an expert cell's accumulate of its counters: after every program,
    # so commoner than the decode program, inside the 50 us between two
    tiny = [("jit_add", b + 0.01, b + 0.02) for _, _, b in device]
    return {
        "modules": sorted(
            ((n, int(a * MS), int(b * MS)) for n, a, b in device + tiny),
            key=lambda m: m[1]),
        "ops": [(int(a * MS), int(b * MS)) for _, a, b in device],
        "host": [(n, int((a + SKEW) * MS), int((b + SKEW) * MS), c)
                 for n, a, b, c in host],
        "lines": {},
    }


def test_decode_executions_are_numbered_by_count_across_the_skew():
    decode, numbered, anchor = join.number_decode_executions(_profile())
    assert decode == "jit_decode"
    modules = _profile()["modules"]
    assert {modules[i][1] // MS: n for i, n in numbered.items()} == {
        0: 101, 10: 102, 25: 103, 38: 104, 48: 105}
    # the late dispatch votes wrongly (its execution had already begun
    # on the device's clock); the commonest offset holds
    assert anchor["dispatch_spans"] == 4 and anchor["agreed"] == 3


def test_a_gap_goes_to_the_cycle_that_issued_the_late_dispatch():
    record = {"cycle": 103, "dispatched": 104, "gc_s": 0.0,
              "descheduled_s": 0.0, "schedule_s": 0.0001,
              "decode_args_s": 0.0001, "dispatch_s": 0.0001,
              "wait_s": 0.00775, "first_wait_s": 0.0, "harvest_s": 0.0177,
              "caller_s": 0.0002}
    profile = _profile()
    _, numbered, _ = join.number_decode_executions(profile)
    rows = join.longest_gaps(profile, numbered, {"slowest": [record]}, n=3)
    assert [r["gap_us"] for r in rows] == [3000.0, 50.0, 50.0]
    late = rows[0]
    assert late["before"] == "decode execution of cycle 104"
    assert late["host_record"]["cycle"] == 103
    assert late["phase_by_count"] == "harvest_s"
    assert late["by_clock"] == "serve/harvest"
    assert {r["before"] for r in rows[1:]} <= {
        "prefill jit_prefill", "decode execution of cycle 102",
        "decode execution of cycle 103", "decode execution of cycle 105"}


def test_without_a_kept_record_the_phases_come_from_the_spans_stats():
    profile = _profile()
    _, numbered, _ = join.number_decode_executions(profile)
    rows = join.longest_gaps(profile, numbered, {"slowest": []}, n=1)
    assert "host_record" not in rows[0]
    assert rows[0]["host_spans_by_stat"] == pytest.approx({
        "harvest_s": 0.0177, "decode_args_s": 0.0001, "dispatch_s": 0.0001,
        "wait_s": 0.00001})
    assert rows[0]["phase_by_count"] == "harvest_s"
