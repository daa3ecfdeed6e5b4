"""The serve cycle's account of itself (PR 38).

A *cycle* is the interval between the arrivals on the host of two
consecutive decode token blocks.  Every decode dispatch has a running
number; the spans of its life carry it as the ``cycle`` stat, and
``ServeMetrics.to_json()["cycles"]`` holds one record for each of the
longest cycles.  Held here, on the CPU and a tiny model, by counts and by
order only (a time is compared with nothing but a constant, by ``>``):

- the numbers are consecutive and a dispatch's ``serve/decode_args``,
  ``serve/dispatch``, ``serve/wait`` and ``serve/harvest`` carry the same
  one in a real profile; ``serve/dispatch`` and ``serve/wait`` lie inside
  ``serve/decode`` in that order;
- ``dispatch_s``, ``wait_s`` and the decode dispatches count the same, a
  record's phases and ``caller_s`` add up to its ``cycle_s``, on engines
  that lag and engines that read at once, slab and paged;
- the cycle that completes a prefill is not plain; the one that only
  dispatched it is;
- the starved decode dispatches follow the readiness probe, and the
  first dispatch after a ``_settle()`` is never asked; a sleep in the
  caller makes the next dispatches count and is found in ``caller_s``; a
  collector pass inside a walk is found in that cycle's ``gc_s``;
- ``reset_metrics()`` clears all of it, the numbering goes on, and the
  collector's hook is installed once however many engines are built.
"""

import gc
import time
import types

import numpy as np
import pytest

from test_obs import _profiled_host_spans
from test_serve_dispatch_args import _engine, _llama
from torchdistx_tpu import obs
from torchdistx_tpu.serve import metrics as serve_metrics
from torchdistx_tpu.serve.metrics import CycleAccount

#: engine kind and ``chunked_prefill``: the first two lag (the fused
#: one-token program with whole prefills), the rest read every dispatch
#: at once
KINDS = {
    "slab": ("slab", None),
    "paged": ("paged", None),
    "slab-chunked": ("slab", 8),
    "paged-chunked": ("paged", 8),
    "speculative": ("speculative", None),
    "persistent": ("persistent", None),
}
LAGGING = ("slab", "paged")
NEW_HISTOGRAMS = ("dispatch_s", "wait_s", "cycle_s", "cycle_plain_s")


def _build(model, case):
    kind, chunked = KINDS[case]
    return _engine(
        model, kind, prefill_buckets=(8, 16), chunked_prefill=chunked
    )


def _prompt(rs, n):
    return rs.randint(0, 256, (n,)).astype(np.int32)


def _adds_up(record):
    phases = sum(record[f"{key}_s"] for key in CycleAccount.KEYS)
    return abs(phases + record["caller_s"] - record["cycle_s"]) < 1e-6


def _records(engine):
    """Every record kept, by cycle number (all of them while fewer than
    ``SLOWEST`` cycles have ended since the metrics began)."""
    cycles = engine.metrics.to_json()["cycles"]
    assert cycles["count"] <= CycleAccount.SLOWEST
    assert len(cycles["slowest"]) == cycles["count"]
    return sorted(cycles["slowest"], key=lambda r: r["cycle"])


@pytest.mark.parametrize("case", list(KINDS))
def test_counts_agree_and_every_record_adds_up(case):
    """Seven requests on three slots, admissions into freed slots: the
    same structure whichever way the engine reads its tokens."""
    engine = _build(_llama(), case)
    rs = np.random.RandomState(38)
    handles = [
        engine.submit(_prompt(rs, n), max_new_tokens=new)
        for n, new in ((5, 9), (11, 4))
    ]
    engine.step()
    engine.step()
    handles += [
        engine.submit(_prompt(rs, n), max_new_tokens=new)
        for n, new in ((9, 6), (4, 12), (13, 3), (6, 7), (7, 5))
    ]
    while engine.step():
        pass
    engine._settle()
    j = engine.metrics.to_json()
    hist, counters, cycles = j["histograms"], j["counters"], j["cycles"]
    dispatches = counters["decode_dispatches"]
    assert dispatches > 0
    assert hist["dispatch_s"]["count"] == dispatches
    assert hist["wait_s"]["count"] == dispatches
    # every dispatch numbered once, in order, from the engine's first
    assert engine._cycle == dispatches
    # a block ends a cycle but for the first since the engine sat idle
    assert 0 < cycles["count"] == hist["cycle_s"]["count"] < dispatches
    assert cycles["plain_count"] == hist["cycle_plain_s"]["count"]
    assert cycles["plain_count"] <= cycles["count"]
    assert 0 < len(cycles["slowest"]) <= CycleAccount.SLOWEST
    longest = [r["cycle_s"] for r in cycles["slowest"]]
    assert longest == sorted(longest, reverse=True)
    for record in cycles["slowest"]:
        assert _adds_up(record), record
        assert 1 <= record["cycle"] <= dispatches
        assert record["riders"] >= 1
        if case in LAGGING:
            # no span straddles an arrival but ``serve/decode``, which is
            # charged nowhere: what no phase covers is never negative,
            # and the dispatch issued inside the interval is the next
            assert record["caller_s"] > -1e-6
            assert record["dispatched"] in (record["cycle"] + 1, None)
        else:
            assert record["dispatched"] == record["cycle"]
    for h in handles:
        r = h.result()
        first, last = r.first_decode_cycle, r.last_decode_cycle
        assert 1 <= first <= last <= dispatches
        assert r.events[-1][0] == "finish"
        assert r.events[-1][2]["first_cycle"] == first
        assert r.events[-1][2]["last_cycle"] == last
        # O(1) entries a request: nothing is logged a tick
        assert len(r.events) <= 8
        assert not [e for e in r.events if e[0] == "decode_chunk"]


@pytest.mark.parametrize("case", ["slab", "speculative"])
def test_a_dispatchs_spans_carry_its_cycle_in_a_real_profile(case, tmp_path):
    engine = _build(_llama(), case)
    rs = np.random.RandomState(11)
    engine.run([{"prompt": _prompt(rs, 5), "max_new_tokens": 3}])  # compiled
    for n in (5, 7):
        engine.submit(_prompt(rs, n), max_new_tokens=12)
    engine.step()  # the admissions, outside the profile
    before = engine._cycle

    def steps():
        for _ in range(5):
            assert engine.step() > 0

    spans = _profiled_host_spans(tmp_path, steps, stats=True)
    # the names are the phases' own: the number is a stat, not a tail
    assert {n for n, *_ in spans} == {
        "serve/schedule", "serve/decode_args", "serve/decode",
        "serve/dispatch", "serve/wait", "serve/harvest",
    }
    dispatched = [s[3]["cycle"] for s in spans if s[0] == "serve/dispatch"]
    assert dispatched == list(range(before + 1, before + 6))
    by_cycle = {}
    for name, t0, t1, stats in spans:
        if "cycle" in stats:
            by_cycle.setdefault(stats["cycle"], {})[name] = (t0, t1)
    whole = [n for n in dispatched if len(by_cycle[n]) == 4]
    # on an engine that lags the last dispatch's block is still due
    assert len(whole) == (4 if case in LAGGING else 5)
    for n in whole:
        life = by_cycle[n]
        order = ["serve/decode_args", "serve/dispatch", "serve/wait",
                 "serve/harvest"]
        assert sorted(life, key=lambda name: life[name][0]) == order
        for a, b in zip(order, order[1:]):  # one after the other
            assert life[a][1] <= life[b][0]
        if case in LAGGING and n + 1 in by_cycle:
            # THE LAG: the next dispatch is issued before this one's
            # block is waited for
            assert (by_cycle[n + 1]["serve/dispatch"][1]
                    <= life["serve/wait"][0])
    # busy, then waited, both inside ``serve/decode``
    decodes = [s for s in spans if s[0] == "serve/decode"]
    assert len(decodes) == 5
    for _, t0, t1, _ in decodes:
        inside = [s[0] for s in spans
                  if t0 <= s[1] and s[2] <= t1 and s[0] != "serve/decode"]
        assert inside == ["serve/dispatch", "serve/wait"]


def test_the_cycle_that_completes_a_prefill_is_not_plain():
    """Under the lag a prefill's device time falls in the cycle AFTER the
    step that dispatched it: that step's own cycle stays plain."""
    engine = _build(_llama(), "slab")
    rs = np.random.RandomState(5)
    engine.submit(_prompt(rs, 6), max_new_tokens=14)
    for _ in range(3):
        engine.step()
    engine.reset_metrics()
    engine.step()  # the first block since the reset ends no cycle
    engine.step()
    engine.submit(_prompt(rs, 7), max_new_tokens=4)
    engine.step()  # dispatches the prefill, then waits for its token
    engine.step()
    engine.step()
    records = _records(engine)
    assert [r["cycle"] for r in records] == list(
        range(records[0]["cycle"], records[0]["cycle"] + 4)
    )
    assert [r["plain"] for r in records] == [True, True, False, True]
    kept_plain = engine.metrics.to_json()["cycles"]["slowest_plain"]
    assert sorted(r["cycle"] for r in kept_plain) == [
        r["cycle"] for r in records if r["plain"]
    ]
    assert [r["admitted"] for r in records] == [0, 1, 0, 0]
    assert [r["prefills"] for r in records] == [0, 0, 1, 0]
    assert [r["first_wait_s"] > 0 for r in records] == [
        False, False, True, False,
    ]
    # the block dispatched by the admitting step carries the newcomer
    assert [r["riders"] for r in records] == [1, 1, 2, 2]
    assert all(_adds_up(r) for r in records)
    j = engine.metrics.to_json()
    assert j["histograms"]["cycle_plain_s"]["count"] == 3
    assert j["histograms"]["prefill_s"]["count"] == 1


def test_starved_dispatches_follow_the_probe_but_never_after_a_settle():
    engine = _build(_llama(), "slab")
    rs = np.random.RandomState(6)
    asked = []
    script = []

    def probe():
        asked.append(True)
        return script.pop(0) if script else False

    engine._device_idle = probe
    engine.submit(_prompt(rs, 6), max_new_tokens=20)
    engine.step()  # a fresh engine: its first dispatch is not asked about
    starved = engine.metrics.cycles.starved
    assert len(asked) == 1  # the decode dispatch behind the prefill was
    assert starved == {"prefill": 0, "decode": 0}
    script[:] = [True, False, True, True, False]
    for _ in range(5):
        engine.step()
    assert len(asked) == 6
    assert starved == {"prefill": 0, "decode": 3}
    engine._settle()
    script[:] = [True, True]
    engine.step()  # nothing was queued: nothing to overlap, not asked
    assert len(asked) == 6
    assert starved == {"prefill": 0, "decode": 3}
    engine.step()
    assert len(asked) == 7
    assert starved == {"prefill": 0, "decode": 4}
    # a prefill behind a decode dispatch in flight is asked about too
    engine.submit(_prompt(rs, 5), max_new_tokens=2)
    engine.step()
    assert len(asked) == 9
    assert starved == {"prefill": 1, "decode": 4}
    # a count that depends on timing stays out of ``counters``, which the
    # session recorder folds into its replay digest integer by integer
    assert not [k for k in engine.metrics.counters if "starved" in k]


def test_a_sleep_in_the_caller_starves_the_device_and_shows_in_caller_s():
    """A tiny CPU program ends long before a 50 ms sleep does: the
    dispatch after it finds the device idle, and the cycle's record puts
    the time where it was spent."""
    engine = _build(_llama(), "slab")
    rs = np.random.RandomState(7)
    engine.submit(_prompt(rs, 6), max_new_tokens=30)
    for _ in range(3):
        engine.step()
    engine.reset_metrics()
    engine.step()
    sleeps = 4
    for _ in range(sleeps):
        time.sleep(0.05)
        engine.step()
    assert engine.metrics.cycles.starved["decode"] >= 2
    slept = engine.metrics.to_json()["cycles"]["slowest"][:sleeps]
    for record in slept:
        assert _adds_up(record)
        assert record["plain"]
        assert record["caller_s"] > 0.045
        assert record["caller_s"] > record["cycle_s"] - record["caller_s"]
        # asleep, the thread neither ran nor waited for the device
        assert record["descheduled_s"] > 0.04
        assert record["gc_s"] == 0 or record["gc_generation"] is not None


def test_a_collector_pass_inside_a_walk_shows_in_that_cycles_gc_s():
    engine = _build(_llama(), "slab")
    rs = np.random.RandomState(8)
    engine.submit(_prompt(rs, 6), max_new_tokens=30)
    for _ in range(3):
        engine.step()
    engine.reset_metrics()
    walk, planted, target = engine._walk, [], None

    def walk_and_collect(flight, *fetched):
        walk(flight, *fetched)
        if flight.cycle == target:
            gc.collect()
            planted.append(flight.cycle)

    engine._walk = walk_and_collect
    gc.disable()  # the planted pass is the only one
    try:
        engine.step()
        target = engine._cycle + 1  # the block walked two steps from here
        for _ in range(4):
            engine.step()
    finally:
        gc.enable()
    assert planted == [target]
    records = {r["cycle"]: r for r in _records(engine)}
    # block ``target`` is walked in the interval that ends with the next
    hit = records[target + 1]
    assert hit["gc_generation"] == 2
    assert hit["harvest_s"] > hit["gc_s"] > 0
    assert _adds_up(hit)
    for n, record in records.items():
        if n != target + 1:
            assert record["gc_s"] == 0 and record["gc_generation"] is None


def test_reset_clears_the_account_and_the_gc_hook_is_installed_once():
    model = _llama()
    engines = [_build(model, case) for case in ("slab", "paged", "slab")]
    assert gc.callbacks.count(serve_metrics._GC_CLOCK) == 1
    engine = engines[0]
    rs = np.random.RandomState(9)
    engine.run([{"prompt": _prompt(rs, 5), "max_new_tokens": 6}])
    assert engine.metrics.to_json()["cycles"]["count"] > 0
    numbered = engine._cycle
    fresh = engine.reset_metrics()
    assert gc.callbacks.count(serve_metrics._GC_CLOCK) == 1
    j = fresh.to_json()
    assert j["cycles"] == {
        "count": 0, "total_s": 0.0, "plain_count": 0, "plain_total_s": 0.0,
        "plain_wait_s": 0.0, "plain_p50_s": None,
        "slow": {"factor": CycleAccount.SLOW_FACTOR, "count": 0,
                 "excess_s": 0.0},
        "starved_dispatches": {"prefill": 0, "decode": 0},
        "slowest": [], "slowest_plain": [],
    }
    for name in NEW_HISTOGRAMS:
        assert j["histograms"][name]["count"] == 0
    # the numbering is the engine's, not the metrics': it goes on
    result = engine.run([{"prompt": _prompt(rs, 4), "max_new_tokens": 3}])[0]
    assert result.first_decode_cycle == numbered + 1


def test_to_json_and_the_prometheus_collector_carry_the_new_histograms():
    engine = _build(_llama(), "slab")
    rs = np.random.RandomState(10)
    engine.run([
        {"prompt": _prompt(rs, n), "max_new_tokens": 8} for n in (5, 7)
    ])
    j = engine.metrics.to_json()
    assert set(NEW_HISTOGRAMS) <= set(j["histograms"])
    assert set(j["cycles"]["starved_dispatches"]) == {"prefill", "decode"}
    flat = engine.metrics.snapshot()
    assert flat["dispatch_s_count"] == j["counters"]["decode_dispatches"]
    registry = obs.MetricsRegistry()
    registry.register_collector(engine.metrics.collector(), obj=engine.metrics)
    samples = obs.parse_prometheus(registry.render())["samples"]
    for base in ("dispatch", "wait", "cycle", "cycle_plain"):
        hist = j["histograms"][f"{base}_s"]
        assert samples[(f"tdx_serve_{base}_seconds_count", ())] == hist["count"]
    for kind, n in j["cycles"]["starved_dispatches"].items():
        key = ("tdx_serve_starved_dispatches_total", (("kind", kind),))
        assert samples[key] == n


def test_the_slow_cycle_sums_count_plain_cycles_over_twice_the_median(
    monkeypatch,
):
    """The account alone, on a clock this test drives: sixteen cycles of
    10 ms set the running median, then one of 35 ms is slow by 25."""
    account = CycleAccount(serve_metrics.Histogram(), serve_metrics.Histogram())
    clock = iter(np.cumsum([0.0] + [0.010] * 17 + [0.035, 0.010]))
    monkeypatch.setattr(serve_metrics, "time", types.SimpleNamespace(
        perf_counter=lambda: float(next(clock)),
        thread_time=time.thread_time,
    ))
    gc.disable()  # the collector's clock reads the same module's time
    try:
        for n in range(20):
            account.arrived(n, 1, n + 1, 0, 0)
    finally:
        gc.enable()
    out = account.to_json()
    assert out["count"] == out["plain_count"] == 19
    assert out["plain_p50_s"] == pytest.approx(0.010)
    assert out["slow"]["count"] == 1
    assert out["slow"]["excess_s"] == pytest.approx(0.025)
    assert out["slowest"][0]["cycle"] == 18
    assert len(out["slowest"]) == CycleAccount.SLOWEST
    assert out["slowest_plain"] == out["slowest"]  # every one was plain
