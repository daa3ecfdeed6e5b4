"""Device cost observatory (obs.cost), HBM capacity planner
(obs.memory.capacity_plan), and dispatch-stall watchdog (obs.watchdog)
— the ISSUE 8 pinned invariants:

- **Card determinism**: two cards of the same program carry bit-identical
  XLA flop/byte counts on a fixed platform — the property that lets the
  perf gate pin them exactly like host_syncs.
- **Single implementation**: ``utils.profiling.cost_summary`` is a
  projection of ``obs.cost.compute_cost_card`` (same numbers, same
  schema as before the refactor).
- **Named provenance**: every card names its peak-bytes source; an
  unnamed source fails schema validation, and a runtime-watermark peak
  never joins the deterministic counter fields.
- **Three exports**: a recorded card is queryable from the book,
  renders as ``tdx_cost_*{program=...}`` through the Prometheus
  registry, lands a Perfetto counter sample on the shared timebase,
  and normalizes into exact-gating ledger counter rows.
- **Capacity planning**: ``capacity_plan`` headroom/fits arithmetic;
  ``sharding_report(budget_bytes_per_device=...)`` per-shard budgets
  (flag-free under budget, ``over_budget`` flag past it).
- **Watchdog**: a simulated expiry (injected fake timer — no sleeping)
  dumps a schema-valid flight record naming the in-flight program AND
  its cost card; a normal exit cancels the timer.

The engine-level admission-gate pins live in tests/test_serve.py
(TestHBMBudgetGate); the dryrun TP leg asserts the per-shard budget
report flag-free in ``__graft_entry__.py``.
"""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchdistx_tpu as tdx
from torchdistx_tpu import obs
from torchdistx_tpu.models import Llama
from torchdistx_tpu.obs.cost import (
    CostBook,
    CostCard,
    compute_cost_card,
    span_mfu,
    validate_cost_card,
)
from torchdistx_tpu.obs.flight import FlightRecorder, validate_flight_jsonl
from torchdistx_tpu.obs.memory import capacity_plan, sharding_report
from torchdistx_tpu.obs.watchdog import DispatchWatchdog
from torchdistx_tpu.serve import ServeEngine
from torchdistx_tpu.utils import profiling


@pytest.fixture
def cards_on(monkeypatch):
    """Re-enable cost-card capture (conftest defaults TDX_COST_CARDS=0
    to keep the suite fast)."""
    monkeypatch.setenv("TDX_COST_CARDS", "1")


def _toy(x):
    return (x @ x).sum()


_X = jnp.ones((32, 32), jnp.float32)


class TestCostCard:
    def test_card_fields_and_schema(self):
        card = compute_cost_card(_toy, _X, name="toy")
        assert card.program == "toy"
        assert card.flops and card.flops > 0
        assert card.bytes_accessed and card.bytes_accessed > 0
        # a backend may leave the peak field at zero (CPU does): the
        # reader must NAME the fallback, never report an unsourced number
        assert card.peak_source in ("xla_peak", "arg+out+temp")
        assert card.peak_bytes and card.peak_bytes > 0
        assert validate_cost_card(card.to_json()) == []

    def test_deterministic_counts(self):
        """The exact-gate premise: same program, same platform ⇒
        bit-identical counts."""
        a = compute_cost_card(_toy, _X, name="a")
        b = compute_cost_card(_toy, _X, name="b")
        assert a.counter_fields() == b.counter_fields()

    def test_flop_attribution(self):
        analytic = 2.0 * 32 * 32 * 32  # the matmul term alone
        card = compute_cost_card(
            _toy, _X, name="toy", analytic_flops=analytic
        )
        # XLA additionally counts the reduction; the ratio must land
        # near 1, not at it
        assert 0.5 < card.flop_attribution < 1.5

    def test_scope_attribution(self):
        """The card records the ENCLOSING recompile scope (what a
        dispatch-path compile would be attributed to), while its own
        compile is attributed to a cost_card/ scope — never confused
        with a real recompile."""
        # a shape no other test compiles, so the card's own compile
        # really happens (a cache hit emits no event); built OUTSIDE
        # the scope — array creation itself is a backend compile
        x = jnp.ones((17, 17))
        watcher = obs.RecompileWatcher()
        try:
            with obs.recompile_scope("serve/decode"):
                card = compute_cost_card(_toy, x, name="scoped")
        finally:
            watcher.uninstall()
        assert card.scope == "serve/decode"
        if watcher.available:
            assert "serve/decode" not in watcher.counts
            assert any(
                k.startswith("cost_card/") for k in watcher.counts
            ), watcher.counts

    def test_watermark_peak_never_gates(self):
        card = CostCard(
            program="p", flops=1.0, bytes_accessed=1.0,
            peak_bytes=123, peak_source="hbm_watermark:host_rusage",
        )
        assert "cost_peak_bytes" not in card.counter_fields()
        assert "cost_flops" in card.counter_fields()

    def test_validate_errors(self):
        errs = validate_cost_card({"schema": "tdx-cost-v1"})
        assert any("program" in e for e in errs)
        assert any("flops" in e for e in errs)
        assert any("source not named" in e for e in errs)

    def test_cost_summary_is_a_projection(self):
        """The satellite refactor: cost_summary delegates to the card
        and keeps its record schema (profile_train_step contract)."""
        card = compute_cost_card(_toy, _X, name="toy")
        out = profiling.cost_summary(_toy, _X, peak_flops=1e12)
        assert out["flops"] == card.flops
        assert out["bytes_accessed"] == card.bytes_accessed
        assert set(out) == {
            "flops", "bytes_accessed", "arithmetic_intensity",
            "output_bytes", "transcendentals", "compute_bound_s",
        }
        assert out["compute_bound_s"] == card.flops / 1e12

    def test_kill_switch_spellings_agree(self, monkeypatch):
        """cards_enabled and force_disabled must read ONE off-list: an
        empty or case-variant TDX_COST_CARDS can never half-engage the
        kill switch (replay sites off but engine/trainer still on)."""
        from torchdistx_tpu.obs.cost import cards_enabled, force_disabled

        for off in ("0", "false", "False", "FALSE", "", " 0 "):
            monkeypatch.setenv("TDX_COST_CARDS", off)
            assert not cards_enabled(default=True)
            assert force_disabled()
        for on in ("1", "true", "yes"):
            monkeypatch.setenv("TDX_COST_CARDS", on)
            assert cards_enabled(default=False)
            assert not force_disabled()
        monkeypatch.delenv("TDX_COST_CARDS")
        assert cards_enabled(default=True) and not cards_enabled(
            default=False
        )
        assert not force_disabled()  # unset = defaults apply, no force

    def test_span_mfu(self):
        card = CostCard(program="p", flops=100.0)
        assert span_mfu(
            card, executions=5, seconds=2.0, peak_flops=1000.0
        ) == pytest.approx(0.25)
        assert span_mfu(
            card, executions=5, seconds=2.0, peak_flops=None
        ) is None


class TestCostBook:
    def test_record_and_query(self):
        book = CostBook()
        compute_cost_card(_toy, _X, name="toy", book=book)
        assert book.get("toy").flops > 0
        assert list(book.to_json()) == ["toy"]
        assert book.max_temp_bytes() == book.get("toy").temp_bytes

    def test_prometheus_projection(self):
        book = CostBook()
        card = compute_cost_card(_toy, _X, name="toy", book=book)
        reg = obs.MetricsRegistry()
        reg.register_collector(book.collector())
        parsed = obs.parse_prometheus(reg.render())
        key = ("tdx_cost_flops", (("program", "toy"),))
        assert parsed["samples"][key] == card.flops
        peak_key = (
            "tdx_cost_peak_bytes",
            (("program", "toy"), ("source", card.peak_source)),
        )
        assert parsed["samples"][peak_key] == card.peak_bytes

    def test_perfetto_counter_track(self):
        t = obs.enable_tracing()
        t.clear()
        try:
            book = CostBook()
            compute_cost_card(_toy, _X, name="toy", book=book)
            counters = [
                ev for ev in t.events()
                if ev["ph"] == "C" and ev["name"] == "cost/toy"
            ]
            assert counters and counters[0]["args"]["flops"] > 0
        finally:
            obs.disable_tracing()
            t.clear()


class TestCapacityPlan:
    def test_fits_arithmetic(self):
        plan = capacity_plan(
            {"weights": 100, "kv_cache": 50}, budget_bytes=200
        )
        assert plan["projected_peak_bytes"] == 150
        assert plan["headroom_bytes"] == 50
        assert plan["fits"] is True
        assert plan["budget_source"] == "explicit"
        assert capacity_plan({"weights": 100}, budget_bytes=99)["fits"] is False

    def test_unknown_budget_is_unknown_not_yes(self):
        # the CPU mesh reports no PJRT bytes_limit: fits must be None
        plan = capacity_plan({"weights": 100})
        assert plan["fits"] is None
        assert plan["headroom_bytes"] is None

    def test_non_numeric_components_dropped(self):
        plan = capacity_plan(
            {"weights": 10, "bogus": None, "flag": True}, budget_bytes=20
        )
        assert plan["components"] == {"weights": 10}

    def test_sharding_report_shard_budget(self):
        params = {"w": jnp.ones((64, 64)), "b": jnp.ones((64,))}
        opt = {"mu['w']": jnp.ones((64, 64))}
        per_dev = (64 * 64 + 64 + 64 * 64) * 4
        rep = sharding_report(
            params, optimizer_state=None,
            budget_bytes_per_device=per_dev + 1000,
        )
        assert rep["shard_budget"]["bytes_per_device"] <= per_dev
        assert rep["shard_budget"]["headroom_bytes"] > 0
        assert not any(f["kind"] == "over_budget" for f in rep["flags"])
        over = sharding_report(
            params, optimizer_state=opt, budget_bytes_per_device=100
        )
        # optimizer state counts toward the per-shard footprint
        assert (
            over["shard_budget"]["bytes_per_device"]
            == over["bytes_per_device"] + over["optimizer_bytes_per_device"]
        )
        assert any(f["kind"] == "over_budget" for f in over["flags"])
        assert over["shard_budget"]["headroom_bytes"] < 0


class _FakeTimer:
    """Injected timer: never sleeps; the test fires it by hand."""

    instances: list = []

    def __init__(self, interval, fn):
        self.interval = interval
        self.fn = fn
        self.started = False
        self.cancelled = False
        _FakeTimer.instances.append(self)

    def start(self):
        self.started = True

    def cancel(self):
        self.cancelled = True

    def fire(self):
        self.fn()


class TestWatchdog:
    def setup_method(self):
        _FakeTimer.instances = []

    def test_expiry_dumps_flight_with_program_and_card(self, tmp_path):
        flight = FlightRecorder(dump_dir=str(tmp_path))
        book = CostBook()
        book.record(
            CostCard(
                program="serve/decode/k4", flops=123.0,
                bytes_accessed=9.0, peak_bytes=7, peak_source="arg+out+temp",
            )
        )
        fake_now = [100.0]
        dog = DispatchWatchdog(
            5.0, flight=flight, book=book,
            clock=lambda: fake_now[0], timer=_FakeTimer,
        )
        with dog.arm("serve/decode/k4"):
            fake_now[0] = 107.5  # the region overran its deadline
            _FakeTimer.instances[-1].fire()
        assert dog.stalls_total == 1
        assert dog.last_dump_path and validate_flight_jsonl(
            dog.last_dump_path
        ) == []
        with open(dog.last_dump_path) as f:
            records = [json.loads(ln) for ln in f if ln.strip()]
        header = records[0]
        assert header["kind"] == "flight_header"
        assert header["reason"] == "watchdog_stall:serve/decode/k4"
        stall = next(r for r in records if r["kind"] == "stall")
        assert stall["program"] == "serve/decode/k4"
        assert stall["armed_s"] == pytest.approx(7.5)
        assert stall["cost_card"]["flops"] == 123.0

    def test_normal_exit_cancels(self, tmp_path):
        flight = FlightRecorder(dump_dir=str(tmp_path))
        dog = DispatchWatchdog(5.0, flight=flight, timer=_FakeTimer)
        with dog.arm("trainer/step"):
            pass
        t = _FakeTimer.instances[-1]
        assert t.started and t.cancelled
        assert dog.stalls_total == 0
        assert dog.last_dump_path is None
        assert dog.last_program == "trainer/step"  # attribution persists

    def test_rejects_nonpositive_timeout(self):
        with pytest.raises(ValueError):
            DispatchWatchdog(0.0)


class TestServeEngineCards:
    def test_every_dispatched_program_has_a_card(self, cards_on):
        tdx.manual_seed(0)
        model = Llama.from_name("tiny", n_kv_heads=2, max_seq_len=64)
        engine = ServeEngine(model, num_slots=2, max_len=64)
        rs = np.random.RandomState(0)
        engine.run(
            [
                {"prompt": rs.randint(0, 64, (6,)).astype(np.int32),
                 "max_new_tokens": 3}
                for _ in range(3)
            ]
        )
        cards = engine.cost_book.cards()
        assert "serve/prefill/b16" in cards
        assert "serve/decode/k1" in cards
        for card in cards.values():
            assert validate_cost_card(card.to_json()) == []
        plan = engine.memory_plan()
        assert plan["components"]["program_temp"] == (
            engine.cost_book.max_temp_bytes()
        )
        assert plan["components"]["kv_cache"] == engine.cache.nbytes
        assert plan["projected_peak_bytes"] == sum(
            plan["components"].values()
        )

    def test_persistent_program_card(self, cards_on):
        tdx.manual_seed(0)
        model = Llama.from_name("tiny", n_kv_heads=2, max_seq_len=64)
        engine = ServeEngine(
            model, num_slots=2, max_len=64,
            decode_mode="persistent", ring_capacity=8,
        )
        engine.run([{"prompt": np.arange(1, 5, dtype=np.int32),
                     "max_new_tokens": 3}])
        assert "serve/decode/persistent/r8" in engine.cost_book.cards()

    def test_kill_switch(self):
        # conftest sets TDX_COST_CARDS=0: the default-on engine must
        # honor the force-disable and capture nothing
        tdx.manual_seed(0)
        model = Llama.from_name("tiny", n_kv_heads=2, max_seq_len=64)
        engine = ServeEngine(model, num_slots=2, max_len=64)
        engine.run([{"prompt": np.arange(1, 5, dtype=np.int32),
                     "max_new_tokens": 2}])
        assert len(engine.cost_book) == 0

    def test_watchdog_attribution_after_run(self, cards_on):
        tdx.manual_seed(0)
        model = Llama.from_name("tiny", n_kv_heads=2, max_seq_len=64)
        engine = ServeEngine(
            model, num_slots=2, max_len=64, stall_timeout_s=300.0
        )
        engine.run([{"prompt": np.arange(1, 5, dtype=np.int32),
                     "max_new_tokens": 2}])
        assert engine.watchdog.stalls_total == 0
        assert engine.watchdog.last_program.startswith("serve/decode")


class TestTrainerCostCard:
    def _fit(self, **kw):
        from torchdistx_tpu.trainer import Trainer

        @jax.jit
        def step(p, s, batch):
            x, y = batch
            loss = jnp.mean((x @ p["w"] - y) ** 2)
            return p, s, loss

        params = {"w": jnp.ones((8, 8))}
        batches = [
            (np.ones((2, 8), np.float32), np.zeros((2, 8), np.float32))
            for _ in range(3)
        ]
        kw.setdefault("peak_flops", 1e9)
        trainer = Trainer(
            step, params, opt_state={}, log_every=1,
            log_fn=lambda m: None, tokens_per_batch=16,
            flops_per_token=64.0, **kw,
        )
        trainer.fit(batches)
        return trainer

    def test_card_and_per_window_mfu_xla(self, cards_on):
        trainer = self._fit()
        assert trainer.cost_card is not None
        assert trainer.cost_card.program == "trainer/step"
        assert trainer.cost_card.flops > 0
        # per-window attribution, not an end-of-run aggregate: both the
        # XLA-counted MFU and the analytic/XLA ratio are live gauges
        assert trainer.metrics["mfu_xla"] > 0
        assert trainer.metrics["flop_attribution"] == (
            trainer.cost_card.flop_attribution
        )
        reg = obs.MetricsRegistry()
        reg.register_collector(trainer.metrics_collector(), obj=trainer)
        parsed = obs.parse_prometheus(reg.render())
        assert ("tdx_train_mfu_xla", ()) in parsed["samples"]

    def test_disabled_by_param(self, cards_on):
        trainer = self._fit(cost_card=False)
        assert trainer.cost_card is None
        assert trainer.metrics["mfu_xla"] is None


class TestLedgerCostRows:
    def _phase(self):
        return {
            "platform": "cpu",
            "model": "tiny",
            "num_slots": 2,
            "decode_chunk": 1,
            "decode_mode": "chunked",
            "metrics": {"counters": {"host_syncs": 3}},
            "cost_cards": {
                "serve/decode/k1": {
                    "schema": "tdx-cost-v1",
                    "program": "serve/decode/k1",
                    "flops": 703242.0,
                    "bytes_accessed": 100.0,
                    "temp_bytes": 7,
                    "peak_bytes": 17,
                    "peak_source": "arg+out+temp",
                },
                "serve/prefill/b16": {
                    "schema": "tdx-cost-v1",
                    "program": "serve/prefill/b16",
                    "flops": 1.0,
                    "bytes_accessed": 2.0,
                    "peak_bytes": 999,
                    "peak_source": "hbm_watermark:host_rusage",
                },
            },
        }

    def test_serve_cards_become_exact_counter_rows(self):
        from torchdistx_tpu.obs.ledger import (
            ingest_serve_record,
            validate_ledger_row,
        )

        rows = ingest_serve_record(
            {"phases": {"k1": self._phase()}}, run_id="r", ts=1.0
        )
        assert all(validate_ledger_row(r) == [] for r in rows)
        cost_rows = [r for r in rows if r["metric"].startswith("cost_")]
        assert all(r["metric_class"] == "counter" for r in cost_rows)
        by = {
            (r["workload"].get("program"), r["metric"]): r["value"]
            for r in cost_rows
        }
        assert by[("serve/decode/k1", "cost_flops")] == 703242.0
        assert by[("serve/decode/k1", "cost_peak_bytes")] == 17
        # a watermark-sourced peak is load-dependent: never a counter
        assert ("serve/prefill/b16", "cost_peak_bytes") not in by
        assert by[("serve/prefill/b16", "cost_flops")] == 1.0
        # program-tagged fingerprints keep per-program pins distinct
        fps = {r["fingerprint"] for r in cost_rows}
        assert len(fps) == 2

    def test_bench_train_card_rows(self):
        from torchdistx_tpu.obs.ledger import ingest_bench_record

        record = {
            "metric": "m", "value": 1.0,
            "extra": {
                "progress": "complete",
                "device": "TFRT_CPU_0",
                "train_model": "tiny",
                "train_cost_card": {
                    "schema": "tdx-cost-v1",
                    "program": "train/step",
                    "flops": 5.0,
                    "bytes_accessed": 6.0,
                    "flop_attribution": 0.9,
                    "peak_source": "arg+out+temp",
                    "peak_bytes": 3,
                },
                "mfu_xla": 0.5,
            },
        }
        rows = ingest_bench_record(record, run_id="r")
        metrics = {r["metric"]: r for r in rows}
        assert metrics["cost_flops"]["value"] == 5.0
        assert metrics["cost_flops"]["metric_class"] == "counter"
        assert metrics["train_flop_attribution"]["value"] == 0.9
        assert metrics["train_flop_attribution"]["metric_class"] == "counter"
        assert metrics["mfu_xla"]["metric_class"] == "timing"

    def test_auto_pins_exclude_buffer_assignment_sizes(self):
        """Machine-written expectations pin the HLO-analysis counts
        (flops/bytes) but not allocator-dependent sizes — those drift
        across XLA versions the way warm-up compile counts do."""
        from torchdistx_tpu.obs.gate import build_expectations
        from torchdistx_tpu.obs.ledger import ingest_serve_record

        rows = ingest_serve_record(
            {"phases": {"k1": self._phase()}}, run_id="r", ts=1.0
        )
        doc = build_expectations(rows)
        pinned = {m for ms in doc["counters"].values() for m in ms}
        assert "cost_flops" in pinned
        assert "cost_bytes_accessed" in pinned
        assert "cost_temp_bytes" not in pinned
        assert "cost_peak_bytes" not in pinned


class TestCostCLI:
    def test_check_obs_artifacts_cost(self, tmp_path):
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = os.path.join(repo, "scripts", "check_obs_artifacts.py")
        good = {
            "phases": {
                "k1": {
                    "cost_cards": {
                        "serve/decode/k1": {
                            "schema": "tdx-cost-v1",
                            "program": "serve/decode/k1",
                            "flops": 1.0,
                            "bytes_accessed": 2.0,
                            "peak_bytes": 3,
                            "peak_source": "arg+out+temp",
                        }
                    }
                }
            }
        }
        p_good = tmp_path / "good.json"
        p_good.write_text(json.dumps(good))
        out = subprocess.run(
            [sys.executable, script, "--cost", str(p_good)],
            capture_output=True, text=True,
        )
        assert out.returncode == 0, out.stderr
        bad = {"phases": {"k1": {"metrics": {}}}}  # no cards, no error
        p_bad = tmp_path / "bad.json"
        p_bad.write_text(json.dumps(bad))
        out = subprocess.run(
            [sys.executable, script, "--cost", str(p_bad)],
            capture_output=True, text=True,
        )
        assert out.returncode == 1
        assert "cost_cards" in out.stderr
