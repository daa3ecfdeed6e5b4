"""Unified tracing & telemetry (torchdistx_tpu.obs) — the pinned invariants:

- **Aggregate/per-request agreement**: the engine's ``ttft_s`` /
  ``e2e_latency_s`` / ``tpot_s`` histograms are fed from the SAME request
  lifecycle timestamps that ``RequestResult`` and the Perfetto
  per-request tracks expose — counts and sums must reconcile exactly.
- **Chrome-trace validity**: ``dump_trace``/``Tracer.export`` emit JSON
  that ``json.load`` parses with a well-formed catapult ``traceEvents``
  list, and each finished request's queued/prefill/decode spans sum to
  its e2e latency.
- **Exposition round-trip**: ``render_prometheus`` output survives the
  stdlib ``parse_prometheus`` with every value intact, and the serve
  collector's numbers equal ``ServeMetrics.to_json()``'s.
- **Recompile accounting**: the watcher counts XLA backend compiles and
  attributes them to the active scope; ``warm_to_steady_state`` with a
  watcher registers EXACTLY the expected donated-carry recompile — one
  extra compile on the second call of a layout-changing carry (simulated
  on CPU, where real donation is a no-op and a donated jit must count
  exactly ONE compile total).
"""

import functools
import json
import os
import re
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchdistx_tpu as tdx
from torchdistx_tpu import obs
from torchdistx_tpu.models import Llama
from torchdistx_tpu.obs.metrics import MetricFamily
from torchdistx_tpu.serve import ServeEngine
from torchdistx_tpu.serve.metrics import Histogram
from torchdistx_tpu.utils import profiling
from torchdistx_tpu.utils.benchmarks import warm_to_steady_state


@pytest.fixture
def tracer():
    """Enabled, empty global tracer; disabled and drained afterwards so
    other tests (and the serve engines they warm) never cross-talk."""
    t = obs.enable_tracing()
    t.clear()
    yield t
    obs.disable_tracing()
    t.clear()


def _llama():
    tdx.manual_seed(0)
    return Llama.from_name("tiny", n_kv_heads=2, max_seq_len=64)


def _prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 256, (n,)).astype(np.int32) for n in lengths]


class TestTracer:
    def test_span_instant_counter_and_export(self, tracer, tmp_path):
        with tracer.span("outer", cat="test", k=1):
            with tracer.span("inner"):
                pass
            tracer.instant("tick", note="x")
        tracer.counter("depth", a=1.0, b=2.0)
        evs = tracer.events()
        # complete events record at span EXIT: inner closes first, the
        # instant fires inside outer, outer closes last
        assert [e["name"] for e in evs] == ["inner", "tick", "outer", "depth"]
        outer = evs[2]
        assert outer["ph"] == "X" and outer["args"] == {"k": 1}
        assert outer["dur"] >= evs[0]["dur"]

        path = tracer.export(str(tmp_path / "trace.json"))
        with open(path) as f:
            doc = json.load(f)
        assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
        for ev in doc["traceEvents"]:
            assert "name" in ev and "ph" in ev and "pid" in ev
            if ev["ph"] == "X":
                assert ev["ts"] >= 0 and ev["dur"] >= 0  # microseconds

    def test_disabled_tracer_records_nothing(self):
        t = obs.get_tracer()
        assert not t.enabled
        before = len(t.events())
        with t.span("ghost"):
            t.instant("ghost")
            t.counter("ghost", v=1)
        assert len(t.events()) == before

    def test_jsonl_sink_streams_parseable_lines(self, tracer, tmp_path):
        path = tracer.open_jsonl(str(tmp_path / "events.jsonl"))
        with tracer.span("a"):
            pass
        tracer.instant("b")
        tracer.close_jsonl()
        lines = [
            json.loads(ln)
            for ln in open(path).read().splitlines()
            if ln.strip()
        ]
        assert [ev["name"] for ev in lines] == ["a", "b"]

    def test_event_cap_counts_drops(self, tmp_path):
        t = obs.Tracer(enabled=True, max_events=2)
        for i in range(5):
            t.instant(f"e{i}")
        assert len(t.events()) == 2
        doc = json.load(open(t.export(str(tmp_path / "t.json"))))
        assert doc["metadata"]["dropped_events"] == 3


class TestPrometheus:
    def test_render_parse_round_trip(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("rt_requests_total", "help text")
        c.inc(3, route="/a")
        c.inc(2.5, route='/b "quoted"\nline')
        g = reg.gauge("rt_depth")
        g.set(7)
        s = reg.summary("rt_lat_seconds")
        s.observe(0.25)
        s.observe(0.75)
        text = reg.render()
        parsed = obs.parse_prometheus(text)
        assert parsed["types"]["rt_requests_total"] == "counter"
        samples = parsed["samples"]
        assert samples[("rt_requests_total", (("route", "/a"),))] == 3
        assert (
            samples[
                ("rt_requests_total", (("route", '/b "quoted"\nline'),))
            ]
            == 2.5
        )
        assert samples[("rt_depth", ())] == 7
        assert samples[("rt_lat_seconds_sum", ())] == 1.0
        assert samples[("rt_lat_seconds_count", ())] == 2

    def test_duplicate_family_rejected(self):
        reg = obs.MetricsRegistry()
        reg.counter("dup_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("dup_total")
        fams = [
            MetricFamily("x", "counter").add(1),
            MetricFamily("x", "counter").add(2),
        ]
        with pytest.raises(ValueError, match="duplicate"):
            obs.render_prometheus(fams)

    def test_parser_rejects_duplicate_samples(self):
        with pytest.raises(ValueError, match="duplicate"):
            obs.parse_prometheus("a 1\na 2\n")

    def test_nonfinite_values_render_as_literals(self):
        """A NaN loss gauge (the trainer's rollback scenario) must render
        as the Prometheus ``NaN`` literal, not crash every scrape."""
        import math

        fams = [
            MetricFamily("nf_loss", "gauge")
            .add(float("nan"))
            .add(float("inf"), suffix="", kind="hi")
            .add(float("-inf"), suffix="", kind="lo"),
        ]
        text = obs.render_prometheus(fams)
        assert "nf_loss NaN" in text
        samples = obs.parse_prometheus(text)["samples"]
        assert math.isnan(samples[("nf_loss", ())])
        assert samples[("nf_loss", (("kind", "hi"),))] == float("inf")
        assert samples[("nf_loss", (("kind", "lo"),))] == float("-inf")

    def test_weakref_collector_drops_with_owner(self):
        class Owner:
            def collect(self):
                return [MetricFamily("owned_total", "counter").add(1)]

        reg = obs.MetricsRegistry()
        owner = Owner()
        reg.register_collector(owner.collect, obj=owner)
        assert "owned_total" in reg.render()
        del owner
        import gc

        gc.collect()
        assert "owned_total" not in reg.render()

    def test_serve_metrics_collector_expires_with_rebind(self):
        """The real-world case the weakref protocol exists for: a bench
        rebinds engine.metrics between passes; the old object's families
        must leave the exposition (else the registry raises on the
        duplicate family names the NEW object also exposes)."""
        import gc

        from torchdistx_tpu.serve.metrics import ServeMetrics

        reg = obs.MetricsRegistry()
        m = ServeMetrics(num_slots=2)
        m.count("requests_submitted", 3)
        reg.register_collector(m.collector(), obj=m)
        assert (
            obs.parse_prometheus(reg.render())["samples"][
                ("tdx_serve_requests_submitted_total", ())
            ]
            == 3
        )
        m = ServeMetrics(num_slots=2)  # the rebind
        gc.collect()
        reg.register_collector(m.collector(), obj=m)
        parsed = obs.parse_prometheus(reg.render())  # no duplicates
        assert parsed["samples"][
            ("tdx_serve_requests_submitted_total", ())
        ] == 0

    def test_http_metrics_endpoint(self):
        reg = obs.MetricsRegistry()
        reg.counter("http_hits_total").inc(5)
        server = obs.start_metrics_server(reg, port=0)
        try:
            port = server.server_address[1]
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as resp:
                assert resp.status == 200
                assert "text/plain" in resp.headers["Content-Type"]
                body = resp.read().decode()
            parsed = obs.parse_prometheus(body)
            assert parsed["samples"][("http_hits_total", ())] == 5
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/nope", timeout=10
                )
        finally:
            server.shutdown()


class TestRecompileWatcher:
    def test_counts_and_attributes_compiles(self):
        x_small = jnp.ones((4,))
        x_big = jnp.ones((8, 8))
        jax.block_until_ready(x_small)
        f = jax.jit(lambda x: x * 2 + 1)
        with obs.RecompileWatcher() as w:
            assert w.available  # jax.monitoring present on this stack
            with obs.recompile_scope("shape_a"):
                jax.block_until_ready(f(x_small))
            with obs.recompile_scope("shape_b"):
                jax.block_until_ready(f(x_big))  # new shape -> new compile
            with obs.recompile_scope("shape_a"):
                jax.block_until_ready(f(x_small))  # cached -> no compile
        assert w.counts["shape_a"] == 1
        assert w.counts["shape_b"] == 1
        assert w.seconds["shape_a"] > 0
        snap = w.snapshot()
        assert snap["compiles_total"] == 2
        assert set(snap["by_scope"]) == {"shape_a", "shape_b"}

    def test_uninstalled_watcher_stops_counting(self):
        w = obs.RecompileWatcher()
        w.uninstall()
        f = jax.jit(lambda x: x - 3)
        jax.block_until_ready(f(jnp.ones((5,))))
        assert w.total == 0

    def test_collector_exposes_per_scope_counters(self):
        with obs.RecompileWatcher() as w:
            with obs.recompile_scope("colfn"):
                jax.block_until_ready(jax.jit(lambda x: x / 2)(jnp.ones(6)))
            reg = obs.MetricsRegistry()
            reg.register_collector(w.collector())
            parsed = obs.parse_prometheus(reg.render())
        key = ("tdx_jit_compiles_total", (("fn", "colfn"),))
        assert parsed["samples"][key] == w.counts["colfn"]

    def test_donated_carry_compiles_once_on_cpu(self):
        """Donation is a no-op on the CPU mesh (CLAUDE.md): the donated
        jit must register EXACTLY one compile and warm_to_steady_state
        must converge on the watcher signal — the baseline against which
        the donation-capable recompile below is the +1."""

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(c):
            return c * 1.5, c.sum()

        carry = jnp.ones((8, 8))
        jax.block_until_ready(carry)
        with obs.RecompileWatcher() as w:
            carry, times, converged = warm_to_steady_state(
                step, carry, sync=float, watcher=w, label="warm"
            )
        assert converged
        assert w.counts["warm"] == 1
        assert len(times) == 2  # compile call + the zero-compile proof

    def test_warm_to_steady_state_registers_donated_carry_recompile(self):
        """THE acceptance pin: the donated-carry double compile —
        call 1 compiles, call 2 recompiles (executable-chosen carry
        layouts on donation-capable backends; simulated here with a
        static-arg flip since CPU donation is a no-op), call 3 runs the
        settled executable — shows up as EXACTLY 2 compiles under the
        warm-up label, and warm_to_steady_state converges on the first
        zero-compile call instead of inferring steadiness from wall
        times."""
        calls = {"n": 0}
        inner = jax.jit(
            lambda c, phase: (c * 2.0, c.sum()), static_argnums=(1,)
        )

        def run(carry):
            calls["n"] += 1
            return inner(carry, min(calls["n"], 2))

        carry = jnp.ones((4, 4))
        jax.block_until_ready(carry)
        with obs.RecompileWatcher() as w:
            carry, times, converged = warm_to_steady_state(
                run, carry, sync=float, watcher=w, label="donated_warm"
            )
        assert converged
        assert calls["n"] == 3  # compile, RECOMPILE, steady proof
        assert w.counts["donated_warm"] == 2
        assert w.snapshot()["by_scope"]["donated_warm"]["compiles"] == 2


class TestProfiling:
    def test_timed_annotation_sink_and_tracer_span(self, tracer):
        seen = []
        with profiling.timed_annotation("obs_region", seen.append) as t:
            time.sleep(0.01)
        assert t["seconds"] >= 0.01
        assert seen == [t["seconds"]]
        spans = [e for e in tracer.events() if e["name"] == "obs_region"]
        assert len(spans) == 1 and spans[0]["cat"] == "dispatch"

    def test_timed_annotation_attributes_compiles(self):
        with obs.RecompileWatcher() as w:
            with profiling.timed_annotation("attr_region"):
                jax.block_until_ready(
                    jax.jit(lambda x: x + 0.5)(jnp.ones((3, 3)))
                )
        assert w.counts.get("attr_region", 0) >= 1

    def test_device_memory_stats_graceful_fallback(self):
        class NoStats:
            def memory_stats(self):
                return None

            def __str__(self):
                return "dev:nostats"

        class Broken:
            def memory_stats(self):
                raise RuntimeError("no PJRT memory stats")

            def __str__(self):
                return "dev:broken"

        stats = profiling.device_memory_stats(NoStats())
        stats.update(profiling.device_memory_stats(Broken()))
        assert stats == {"dev:nostats": {}, "dev:broken": {}}
        text = profiling.format_memory_stats(stats)
        assert text.count("(no memory stats)") == 2
        rich = profiling.format_memory_stats(
            {"dev:ok": {"bytes_in_use": 2e9, "peak_bytes_in_use": 3e9,
                        "bytes_limit": 16e9}}
        )
        assert "2.00 GB in use" in rich and "peak 3.00 GB" in rich

    def test_device_memory_stats_real_devices(self):
        stats = profiling.device_memory_stats()
        assert len(stats) == len(jax.devices())
        assert all(isinstance(s, dict) for s in stats.values())
        assert isinstance(profiling.format_memory_stats(stats), str)

    def test_cost_summary_tiny_jitted_fn(self):
        x = jnp.ones((16, 16), jnp.float32)
        out = profiling.cost_summary(
            jax.jit(lambda a: a @ a), x, peak_flops=1e12
        )
        assert set(out) >= {
            "flops",
            "bytes_accessed",
            "arithmetic_intensity",
            "compute_bound_s",
        }
        assert out["flops"] > 0  # a 16x16 matmul is not free
        assert out["compute_bound_s"] == out["flops"] / 1e12


class TestHistogramWindow:
    def test_window_count_vs_lifetime_count(self):
        h = Histogram(maxlen=10)
        for v in range(100):
            h.record(float(v))
        s = h.snapshot()
        assert s["count"] == 100  # lifetime, exact
        assert abs(s["mean"] - 49.5) < 1e-9  # lifetime, exact
        assert s["window_count"] == h.window_count <= 10
        # quantiles/max describe the recent window only: every sample
        # still in the reservoir is from the tail of the stream
        assert s["p50"] >= 90 and s["max"] == 99.0

    def test_window_equals_count_before_overflow(self):
        h = Histogram(maxlen=10)
        for v in (1.0, 2.0):
            h.record(v)
        s = h.snapshot()
        assert s["window_count"] == s["count"] == 2


class TestServeIntegration:
    def _run_engine(self, tracer, n=6):
        engine = ServeEngine(_llama(), num_slots=2, max_len=32)
        reqs = [
            {"prompt": p, "max_new_tokens": 4, "seed": i}
            for i, p in enumerate(_prompts(3, [3, 5, 2, 7, 4, 6][:n]))
        ]
        results = engine.run(reqs)
        return engine, results

    def test_aggregates_agree_with_per_request_views(self, tracer):
        engine, results = self._run_engine(tracer)
        finished = engine.finished_requests()
        assert len(finished) == len(results) == 6
        m = engine.metrics
        # counts: one histogram entry per finished request
        assert m.ttft_s.count == m.e2e_latency_s.count == 6
        # sums: the aggregates were fed from the requests' own lifecycle
        # timestamps, so per-request derived values reconcile exactly
        assert sum(r.ttft_s for r in results) == pytest.approx(
            m.ttft_s.total, rel=1e-9
        )
        assert sum(r.latency_s for r in results) == pytest.approx(
            m.e2e_latency_s.total, rel=1e-9
        )
        assert sum(r.queue_wait_s for r in results) == pytest.approx(
            m.queue_wait_s.total, rel=1e-9
        )
        tpots = [r.tpot_s for r in results if r.tpot_s is not None]
        assert len(tpots) == m.tpot_s.count
        assert sum(tpots) == pytest.approx(m.tpot_s.total, rel=1e-9)

    def test_lifecycle_events_ordered_and_complete(self, tracer):
        engine, results = self._run_engine(tracer)
        for req in engine.finished_requests():
            names = [e[0] for e in req.events]
            # causal order: submit -> admitted -> prefill -> first_token
            # -> decode chunks -> finish
            for a, b in zip(
                ["submit", "admitted", "prefill", "first_token"],
                names[:4],
            ):
                assert a == b, names
            assert names[-1] == "finish"
            times = [e[1] for e in req.events]
            assert times == sorted(times)
            # every event timestamp is JSON-able data
            json.dumps(req.events)

    def test_dump_trace_valid_and_spans_sum_to_e2e(self, tracer, tmp_path):
        engine, results = self._run_engine(tracer)
        path = engine.dump_trace(str(tmp_path / "serve_trace.json"))
        with open(path) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        assert all("name" in e and "ph" in e for e in evs)
        # the engine's dispatch spans made it in: on this engine, which
        # reads its tokens a dispatch late, a prefill has two (its
        # dispatch, and the wait for its first token at the step's end)
        # and a decode dispatch one, with one more for each fetch that
        # issued nothing (``_settle``)
        m = engine.metrics
        assert engine._lags
        assert (
            len([e for e in evs if e["name"] == "serve/prefill"])
            == 2 * m.counters["prefill_calls"]
        )
        assert (
            len([e for e in evs if e["name"] == "serve/decode"])
            == m.decode_s.count >= m.counters["decode_dispatches"]
        )
        # per-request tracks: queued + prefill + decode spans sum to the
        # request's e2e latency (same timestamps as e2e_latency_s)
        by_req: dict = {}
        for e in evs:
            if e.get("cat") == "request" and e["ph"] == "X":
                by_req.setdefault(e["args"]["rid"], []).append(e)
        assert len(by_req) == 6
        for req in engine.finished_requests():
            spans = by_req[req.rid]
            assert {s["name"] for s in spans} == {
                "queued",
                "prefill",
                "decode",
            }
            total_us = sum(s["dur"] for s in spans)
            e2e_us = (req.finished_at - req.submitted_at) * 1e6
            assert total_us == pytest.approx(e2e_us, abs=0.01)

    def test_exposition_matches_to_json(self, tracer):
        engine, _ = self._run_engine(tracer)
        registry = obs.MetricsRegistry()
        registry.register_collector(
            engine.metrics.collector(), obj=engine.metrics
        )
        parsed = obs.parse_prometheus(registry.render())
        j = engine.metrics.to_json()
        for name, v in j["counters"].items():
            assert (
                parsed["samples"][(f"tdx_serve_{name}_total", ())] == v
            ), name
        for name, v in j["gauges"].items():
            assert parsed["samples"][(f"tdx_serve_{name}", ())] == v, name
        # summaries: lifetime count/sum + window quantiles
        assert (
            parsed["samples"][("tdx_serve_ttft_seconds_count", ())]
            == engine.metrics.ttft_s.count
        )
        assert parsed["samples"][
            ("tdx_serve_ttft_seconds_sum", ())
        ] == pytest.approx(engine.metrics.ttft_s.total, rel=1e-6)
        assert parsed["types"]["tdx_serve_ttft_seconds"] == "summary"

    def test_finished_history_bounded_and_disableable(self, tracer):
        engine = ServeEngine(
            _llama(), num_slots=2, max_len=32, finished_history=2
        )
        engine.run(
            [{"prompt": p, "max_new_tokens": 2} for p in _prompts(5, [3] * 5)]
        )
        kept = engine.finished_requests()
        assert len(kept) == 2  # newest two only
        assert kept[-1].rid == 4
        engine_off = ServeEngine(
            _llama(), num_slots=2, max_len=32, finished_history=0
        )
        results = engine_off.run(
            [{"prompt": p, "max_new_tokens": 2} for p in _prompts(5, [3, 4])]
        )
        assert engine_off.finished_requests() == []
        # lifecycle events still ride out on the results themselves
        assert all(r.events[-1][0] == "finish" for r in results)

    def test_expired_request_gets_partial_track(self, tracer, tmp_path):
        engine = ServeEngine(_llama(), num_slots=1, max_len=32)
        # one request hogs the single slot; the second expires queued
        engine.submit(
            np.ones(3, np.int32), max_new_tokens=8, deadline_s=1e6
        )
        h2 = engine.submit(
            np.ones(4, np.int32), max_new_tokens=8, deadline_s=0.0
        )
        while engine.step():
            pass
        assert h2.result().finish_reason == "deadline"
        names = [e[0] for e in h2.result().events]
        assert names == ["submit", "expire"]
        doc = json.load(
            open(engine.dump_trace(str(tmp_path / "expired.json")))
        )
        rows = [
            e
            for e in doc["traceEvents"]
            if e.get("cat") == "request"
            and e.get("args", {}).get("rid") == h2.rid
        ]
        assert [e["name"] for e in rows] == ["queued"]


class TestTrainerTelemetry:
    def test_fit_spans_and_collector(self, tracer):
        from torchdistx_tpu.trainer import Trainer

        def step(params, opt_state, batch):
            return params, opt_state, jnp.float32(0.25)

        logs = []
        t = Trainer(
            step,
            params={},
            opt_state={},
            tokens_per_batch=16,
            log_every=1,
            log_fn=logs.append,
        )
        t.fit([None] * 3, num_steps=3)
        assert t.metrics["steps_total"] == 3
        assert t.metrics["tokens_total"] == 48
        assert t.metrics["loss"] == pytest.approx(0.25)
        spans = [
            e for e in tracer.events() if e["name"] == "trainer/step"
        ]
        assert len(spans) == 3
        reg = obs.MetricsRegistry()
        reg.register_collector(t.metrics_collector(), obj=t)
        parsed = obs.parse_prometheus(reg.render())
        assert parsed["samples"][("tdx_train_steps_total", ())] == 3
        assert parsed["samples"][("tdx_train_tokens_total", ())] == 48
        assert parsed["samples"][
            ("tdx_train_loss", ())
        ] == pytest.approx(0.25)


class TestReplaySpans:
    def test_materialize_emits_replay_spans(self, tracer):
        model = tdx.deferred_init(
            lambda: Llama.from_name("tiny", n_kv_heads=2, max_seq_len=32)
        )
        tdx.materialize_module(model)
        names = [e["name"] for e in tracer.events()]
        assert "materialize_module" in names
        assert any(n.startswith("replay/") for n in names)


SERVE_LEAVES = [
    "serve/schedule", "serve/decode_args", "serve/decode", "serve/harvest",
]
#: spans inside those: a prefill in ``serve/schedule``; the call and the
#: blocked read in ``serve/decode``
SERVE_CHILDREN = ["serve/prefill", "serve/dispatch", "serve/wait"]


def _profiled_host_spans(tmp_path, body, stats=False):
    """Run ``body()`` under a real ``jax.profiler`` trace and read the
    host plane back: ``[(name, start_ns, end_ns)]`` of every span under
    the program's prefixes, by start (outer before inner); with
    ``stats`` each followed by its annotation's stats as a dict."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    found = sorted(tmp_path.rglob("*.xplane.pb"))
    assert found, "the profiler wrote no trace"
    data = jax.profiler.ProfileData.from_file(str(found[-1]))
    spans = [
        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
        + ((dict(e.stats),) if stats else ())
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith(("serve/", "trainer/"))
    ]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


class TestOneSpanPrimitive:
    """ISSUE 27: every span is a profiler annotation, once, whether or
    not the tracer records; the phases of a serve step and of a train
    step land in a profile under exactly their names."""

    @pytest.fixture
    def entered(self, monkeypatch):
        """Names of the profiler annotations entered, in order."""
        names = []

        class Annotation:
            def __init__(self, name, **kwargs):
                self.name = name

            def __enter__(self):
                names.append(self.name)

            def __exit__(self, *exc):
                return None

        monkeypatch.setattr(obs.trace, "TraceAnnotation", Annotation)
        monkeypatch.setattr(obs.trace, "StepTraceAnnotation", Annotation)
        return names

    def test_disabled_tracer_still_annotates_once(self, entered):
        tracer = obs.get_tracer()
        assert not tracer.enabled
        with tracer.span("obs/plain", cat="x", detail=3):
            pass
        with tracer.span("obs/step", step_num=7):
            pass
        with profiling.timed_annotation("obs/timed") as t:
            pass
        assert entered == ["obs/plain", "obs/step", "obs/timed"]
        assert t["seconds"] >= 0.0
        assert tracer.events() == []

    def test_enabled_tracer_annotates_once_and_records(self, entered, tracer):
        with profiling.timed_annotation("obs/timed"):
            pass
        with tracer.span("obs/step", cat="trainer", step_num=7):
            pass
        assert entered == ["obs/timed", "obs/step"]
        events = {e["name"]: e for e in tracer.events()}
        assert set(events) == {"obs/timed", "obs/step"}
        assert events["obs/step"]["args"] == {"step_num": 7}

    def test_failing_sink_still_exits_the_annotation(self, monkeypatch, tracer):
        exited = []

        class Annotation:
            def __init__(self, name, **kwargs):
                self.name = name

            def __enter__(self):
                return None

            def __exit__(self, *exc):
                exited.append(self.name)

        def full_disk(event):
            raise OSError("no space left on device")

        monkeypatch.setattr(obs.trace, "TraceAnnotation", Annotation)
        monkeypatch.setattr(tracer, "_add", full_disk)
        with pytest.raises(OSError):
            with tracer.span("obs/sink_fails"):
                pass
        assert exited == ["obs/sink_fails"]

    def test_serve_step_phases_in_a_real_profile(self, tmp_path):
        engine = ServeEngine(_llama(), num_slots=2, max_len=32)
        prompts = _prompts(11, [5, 3, 4, 6])
        engine.run([{"prompt": p, "max_new_tokens": 3} for p in prompts[:2]])
        for p in prompts[2:]:
            engine.submit(p, max_new_tokens=8)

        def three_steps():
            for _ in range(3):
                assert engine.step() > 0

        spans = _profiled_host_spans(tmp_path, three_steps)
        names = [n for n, _, _ in spans]
        # exactly these names: no ``#k=v#`` tail, no per-step label
        # (``cycle=n`` rides as a stat: tests/test_serve_cycle_account.py)
        assert set(names) == set(SERVE_LEAVES) | set(SERVE_CHILDREN)
        ends = [i for i, n in enumerate(names) if n == "serve/harvest"]
        assert len(ends) == 3
        for k, i in enumerate(ends):
            step = spans[ends[k - 1] + 1 if k else 0 : i + 1]
            leaves = [s for s in step if s[0] not in SERVE_CHILDREN]
            # flat leaves in order, harvest last: nothing of the step
            # is left after it (nothing timed).  The step that admitted
            # has a second ``serve/schedule`` after its decode dispatch:
            # the waits for the first tokens, the dispatch queued behind
            assert [n for n, _, _ in leaves] == (
                SERVE_LEAVES[:3] + ["serve/schedule", "serve/harvest"]
                if k == 0 else SERVE_LEAVES
            )
            prefills = [s for s in step if s[0] == "serve/prefill"]
            assert len(prefills) == (4 if k == 0 else 0)
            # children of the schedule phases: two dispatches in the
            # first, their two waits in the second
            for j, (_, t0, t1) in enumerate(prefills):
                schedule = leaves[0] if j < 2 else leaves[3]
                assert schedule[1] <= t0 and t1 <= schedule[2]
            for a, b in zip(leaves, leaves[1:]):  # no overlap
                assert a[2] <= b[1]

    def test_trainer_fit_phases_in_a_real_profile(self, tmp_path):
        from torchdistx_tpu.trainer import Trainer

        step = jax.jit(lambda p, o, b: (p, o, jnp.sum(b)))
        t = Trainer(step, params={}, opt_state={}, log_every=1,
                    log_fn=lambda m: None)
        batches = [jnp.ones((4,)) * i for i in range(3)]
        t.fit(batches[:1], num_steps=1)  # the compiling step, outside
        spans = _profiled_host_spans(
            tmp_path, lambda: t.fit(iter(batches[1:]), num_steps=3)
        )
        names = [n for n, _, _ in spans]
        assert names == [
            "trainer/next_batch", "trainer/step", "trainer/sync",
            "trainer/next_batch", "trainer/step", "trainer/sync",
        ]
        for a, b in zip(spans, spans[1:]):
            assert a[2] <= b[1]

    def test_phase_histograms_in_to_json_and_reset(self):
        engine = ServeEngine(_llama(), num_slots=2, max_len=32)
        engine.run([{"prompt": p, "max_new_tokens": 3}
                    for p in _prompts(5, [4, 6, 3])])
        phases = ("schedule_s", "decode_args_s", "harvest_s")
        hists = engine.metrics.to_json()["histograms"]
        dispatches = engine.metrics.counters["decode_dispatches"]
        assert dispatches > 0
        for name in phases:
            assert hists[name]["count"] > 0, name
        assert hists["decode_args_s"]["count"] == dispatches
        # one more of each for a fetch and walk that issued nothing
        # (``_settle``: the step that found the last dispatch in flight)
        assert hists["harvest_s"]["count"] == hists["decode_s"]["count"]
        assert dispatches < hists["decode_s"]["count"] <= dispatches + 2
        engine.reset_metrics()
        hists = engine.metrics.to_json()["histograms"]
        for name in phases:
            assert hists[name]["count"] == 0, name

    def test_named_scopes_reach_the_lowered_train_step(self):
        from torchdistx_tpu.nn import functional as F
        from torchdistx_tpu.nn.module import functional_call
        from torchdistx_tpu.optimizers import anyprecision_adamw
        from torchdistx_tpu.parallel import ShardedTrainStep, create_mesh

        model = _llama()
        params = dict(model.named_parameters())

        def loss_fn(p, batch):
            tokens, labels = batch
            return F.cross_entropy(
                functional_call(model, p, (tokens,)), labels
            )

        mesh = create_mesh({"fsdp": 1}, devices=jax.devices()[:1])
        step = ShardedTrainStep(
            loss_fn, anyprecision_adamw(1e-4), mesh, shard_axis="fsdp"
        )
        opt_state = step.init_optimizer(params)
        tokens = jnp.zeros((2, 16), jnp.int32)
        text = jax.jit(lambda p, o, b: step(p, o, b)).lower(
            params, opt_state, (tokens, tokens)
        ).as_text(debug_info=True)
        # metadata only: the scopes are path components of op names
        for scope in ("attention", "mlp", "vocab_projection", "loss",
                      "optimizer"):
            assert re.search(rf'[/"(]{scope}[/")]', text), scope
