"""The readers of the serve loop's cycle account (``metrics/cycle_readers.py``):
each on a metrics object whose numbers are worked by hand, None on a
program that lacks the field (the parent of PR 38), the real
``ServeMetrics`` of a tiny engine on the CPU, and every serve cell still
loading with them, file for file."""

import os
import types

import pytest
from harness import loader

READERS = loader.load_module(
    os.path.join(loader.ROOT, "metrics", "cycle_readers.py"), "metric reader module")
SERVE_CELLS = ["mistral-7b.batch16", "kanana-2-30b.batch32-8k",
               "jamba2-3b.batch256", "qwen3-next-80b.batch128-4k"]
NAMES = ["serve.decode_cycle_ms_p50", "serve.host_busy_pct",
         "serve.starved_dispatch_pct", "serve.prefill_share_pct",
         "serve.slow_cycle_share_pct", "serve.lagged_slot_steps_pct"]


def _hist(total, p50=None):
    return types.SimpleNamespace(total=total, quantile=lambda q: p50)


def _window():
    """A window of 1,000 plain cycles of 16 ms and 50 that held a prefill:
    the host waited 14 ms of each plain one; two plain cycles were slow,
    by 30 ms together; 12 of 1,250 dispatches found the device idle; 180
    of 1,050 x 16 slot-steps ran frozen."""
    return types.SimpleNamespace(
        num_slots=16,
        cycle_s=_hist(16.0 + 50 * 0.060),
        cycle_plain_s=_hist(16.0, p50=0.0161),
        prefill_s=_hist(200 * 0.0205),
        cycles=types.SimpleNamespace(plain_wait_s=14.0, slow_excess_s=0.030,
                                     starved={"prefill": 2, "decode": 10}),
        counters={"decode_dispatches": 1050,
                  "prefill_calls": 200, "lagged_slot_steps": 180,
                  "decode_steps": 1050},
    )


@pytest.fixture
def latest(monkeypatch):
    from torchdistx_tpu.serve import metrics

    def put(m):
        monkeypatch.setattr(metrics, "latest_metrics", lambda: m)
    return put


@pytest.mark.parametrize("name, expected", [
    ("serve_decode_cycle_ms_p50", 16.1),
    ("serve_host_busy_pct", 100 * (1 - 14.0 / 16.0)),
    ("serve_starved_dispatch_pct", 100 * 12 / 1250),
    ("serve_prefill_share_pct", 100 * 4.1 / 19.0),
    ("serve_slow_cycle_share_pct", 100 * 0.030 / 16.0),
    ("serve_lagged_slot_steps_pct", 100 * 180 / (1050 * 16)),
])
def test_reader_on_a_window_worked_by_hand(latest, name, expected):
    latest(_window())
    assert getattr(READERS, name)(None) == pytest.approx(expected)


@pytest.mark.parametrize("name", [n.replace(".", "_") for n in NAMES])
@pytest.mark.parametrize("program", ["none", "parent", "empty"])
def test_reader_finds_nothing_and_does_not_raise(latest, name, program):
    """No metrics at all; the parent's, which has counters and
    ``prefill_s`` but no account; the change's before any cycle ended."""
    m = {
        "none": None,
        "parent": types.SimpleNamespace(
            num_slots=16, prefill_s=_hist(4.1),
            counters={"decode_dispatches": 1050, "prefill_calls": 200}),
        "empty": types.SimpleNamespace(
            num_slots=16, cycle_s=_hist(0.0), cycle_plain_s=_hist(0.0),
            prefill_s=_hist(0.0),
            cycles=types.SimpleNamespace(plain_wait_s=0.0, slow_excess_s=0.0,
                                         starved={"prefill": 0, "decode": 0}),
            counters={"decode_dispatches": 0,
                      "prefill_calls": 0, "lagged_slot_steps": 0,
                      "decode_steps": 0}),
    }[program]
    latest(m)
    assert getattr(READERS, name)(None) is None


def test_readers_on_a_real_engine(drive):
    """A rehearsal cell through ``run.main``: the program's own
    ``ServeMetrics`` is the latest, and every reader reads it (shares
    between 0 and 100; never reported: the CPU gives counts only)."""
    from torchdistx_tpu.serve.metrics import latest_metrics

    drive("tiny.batch4")
    m = latest_metrics()
    assert m.cycle_s.count > 0
    assert m.dispatch_s.count == m.wait_s.count == m.counters["decode_dispatches"]
    for name in NAMES:
        value = getattr(READERS, name.replace(".", "_"))(None)
        assert value is not None and value >= 0.0
        if name.endswith("_pct") and name != "serve.slow_cycle_share_pct":
            assert value <= 100.0


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serve_cells_load_with_the_six_metrics(cell):
    loaded = {m.name: m for m in loader.load_cell(cell).per_layer}
    assert set(NAMES) <= set(loaded)
    for name in NAMES:
        m = loaded[name]
        assert m.moves == "serve_tokens_per_s" and m.better == "lower"
        assert m.reader.__name__ == name.replace(".", "_")
    train = {m.name for m in loader.load_cell("dscoder-1.3b.train").per_layer}
    assert not set(NAMES) & train
