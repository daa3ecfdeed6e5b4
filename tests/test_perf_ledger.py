"""Perf sentinel (obs/ledger.py + obs/gate.py + scripts/perf_gate.py) —
the pinned invariants:

- **Ingest round-trip per artifact family**: every committed artifact
  family (bench wrapper, bare bench record, serve record, multichip,
  kernel-acceptance, flight dump) normalizes into schema-valid
  ``tdx-ledger-v1`` rows, and the real committed artifacts at the repo
  root backfill into a populated trajectory (r03 + serve + multichip),
  with the failed round (r03) carrying ``quality: degraded``.
- **Exact counter gate**: expectations pinned from a record PASS against
  the same record; perturbing ANY pinned counter by +1 fails the gate —
  and ``scripts/perf_gate.py --strict`` exits nonzero naming the metric.
- **Timing bands are direction-aware**: a tok/s drop beyond tolerance
  fails, a tok/s gain passes; a seconds increase beyond tolerance fails,
  a seconds decrease passes.
- **Degraded rows never baseline**: a degraded ledger row with a better
  value than every complete row must not become the comparison point.
"""

import json
import os
import subprocess
import sys

import pytest

from torchdistx_tpu.obs import gate as gate_mod
from torchdistx_tpu.obs import ledger as ledger_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")


# --------------------------------------------------------------------------
# synthetic records, one per artifact family (tiny, no engine runs)
# --------------------------------------------------------------------------

def serve_record(host_syncs=12, decode_dispatches=10, error=None):
    phase = {
        "bench": "serve",
        "model": "tiny",
        "platform": "cpu",
        "requests": 6,
        "max_new_tokens": 8,
        "num_slots": 2,
        "decode_chunk": 4,
        "decode_mode": "chunked",
        "max_len": 64,
        "drain_wall_s": 0.21,
        "compiled_programs": 3,
        "recompile_measure": {"available": True, "compiles_total": 0},
        "recompile_warmup": {"available": True, "compiles_total": 7},
        "metrics": {
            "counters": {
                "requests_completed": 6,
                "tokens_generated": 48,
                "tokens_decoded": 42,
                "decode_dispatches": decode_dispatches,
                "host_syncs": host_syncs,
                "masked_slot_steps": 0,
            },
            "gauges": {"num_slots": 2},
            "histograms": {
                "ttft_s": {"count": 6, "p50": 0.03, "p95": 0.05},
                "decode_token_s": {"count": 42, "p50": 0.004, "p95": 0.006},
            },
            "derived": {
                "wall_s": 0.5,
                "decode_tokens_per_sec": 200.0,
                "wall_tokens_per_sec": 96.0,
                "syncs_per_token": host_syncs / 48,
                "prefix_hit_rate": None,
            },
        },
    }
    if error:
        phase = {"error": error}
    return {
        "bench": "serve",
        "record_schema": "tdx-record-v1",
        "git_sha": "feedfacecafe",
        "model": "tiny",
        "phases": {"k4": phase},
    }


def bench_record(progress="complete", tokens_per_sec=19515.6):
    return {
        "metric": "deferred_init_materialize_llama2_7b_wall_s",
        "git_sha": "feedfacecafe",
        "value": 13.3,
        "vs_baseline": 4.5,
        "tokens_per_sec": tokens_per_sec,
        "mfu": 0.65,
        "goodput": 0.9,
        "extra": {
            "progress": progress,
            "deferred_init_s": 3.0,
            "materialize_s": 10.3,
            "params": 6738415616,
            "peak_host_rss_gb": 0.25,
            "device": "TPU v5 lite0",
            "train_model": "llama_1b",
            "train_batch": 2,
            "train_seq": 2048,
            "train_window_s": 4.2,
            "train_recompile": {
                "available": True,
                "compiles_total": 3,
                "by_scope": {
                    "warmup": {"compiles": 3},
                    "timed_window": {"compiles": 0},
                },
            },
            "remat": False,
            "optimizer": "anyprecision_adamw",
            "materialize_chunked": {"total_s": 14.9, "materialize_s": 12.1},
        },
    }


def multichip_record(ok=True):
    tail = (
        "dryrun_multichip(8): mesh dp=2 fsdp=2 sp=2, step OK\n"
        'MULTICHIP_LEG {"leg": "fsdp_sp", "seconds": 3.2, "comm_ops": 12, '
        '"comm_bytes_by_axis": {"fsdp": 1024, "sp": 512}, "compiles": 4}\n'
        "dryrun_multichip(8): TP leg OK\n"
    )
    return {"n_devices": 8, "rc": 0 if ok else 1, "ok": ok,
            "skipped": False, "tail": tail}


# --------------------------------------------------------------------------
# row schema + ledger file plumbing
# --------------------------------------------------------------------------

class TestLedgerRows:
    def test_make_row_validates(self):
        row = ledger_mod.make_row(
            run_id="r", source="bench", metric="m", value=1,
            metric_class="counter", quality="complete",
            workload={"phase": "x"},
        )
        assert ledger_mod.validate_ledger_row(row) == []
        assert row["fingerprint"] == "phase=x"

    @pytest.mark.parametrize(
        "patch",
        [
            {"schema": "tdx-ledger-v0"},
            {"source": "mystery"},
            {"metric_class": "vibes"},
            {"quality": "great"},
            {"value": "fast"},
            {"value": float("nan")},
            {"fingerprint": "phase=y"},
        ],
    )
    def test_bad_rows_flagged(self, patch):
        row = ledger_mod.make_row(
            run_id="r", source="bench", metric="m", value=1,
            metric_class="counter", quality="complete",
            workload={"phase": "x"},
        )
        row.update(patch)
        assert ledger_mod.validate_ledger_row(row)

    def test_fingerprint_is_order_independent_and_int_normalized(self):
        a = ledger_mod.fingerprint({"b": 2, "a": 1})
        b = ledger_mod.fingerprint({"a": 1, "b": 2.0})
        assert a == b == "a=1|b=2"

    def test_append_read_roundtrip(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        rows = [
            ledger_mod.make_row(
                run_id=f"r{i}", source="bench", metric="m", value=i,
                metric_class="counter", quality="complete",
            )
            for i in range(3)
        ]
        assert ledger_mod.append_rows(path, rows) == 3
        assert ledger_mod.append_rows(path, rows[:1]) == 1  # append-only
        back = ledger_mod.read_ledger(path)
        assert [r["value"] for r in back] == [0, 1, 2, 0]
        assert ledger_mod.validate_ledger_file(path) == []

    def test_append_rejects_invalid(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        bad = ledger_mod.make_row(
            run_id="r", source="bench", metric="m", value=1,
            metric_class="counter", quality="complete",
        )
        bad["value"] = "fast"
        with pytest.raises(ValueError):
            ledger_mod.append_rows(path, [bad])
        assert not os.path.exists(path)

    def test_whitespace_only_ledger_fails_validation(self, tmp_path):
        path = tmp_path / "empty.jsonl"
        path.write_text("\n\n")
        assert ledger_mod.validate_ledger_file(str(path))

    def test_read_skips_corrupt_tail_validate_flags_it(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        ledger_mod.append_rows(
            path,
            [ledger_mod.make_row(
                run_id="r", source="bench", metric="m", value=1,
                metric_class="counter", quality="complete")],
        )
        with open(path, "a") as f:
            f.write('{"truncated": ')  # killed-run torn write
        assert len(ledger_mod.read_ledger(path)) == 1
        assert ledger_mod.validate_ledger_file(path)


# --------------------------------------------------------------------------
# ingest adapters, one per family
# --------------------------------------------------------------------------

class TestIngest:
    def test_serve_counters_and_classes(self):
        rows = ledger_mod.ingest_serve_record(serve_record(), run_id="s1")
        assert rows and all(
            not ledger_mod.validate_ledger_row(r) for r in rows
        )
        by = {r["metric"]: r for r in rows}
        assert by["host_syncs"]["value"] == 12
        assert by["host_syncs"]["metric_class"] == "counter"
        assert by["syncs_per_token"]["metric_class"] == "counter"
        assert by["decode_tokens_per_sec"]["metric_class"] == "timing"
        assert by["recompile_measure_compiles"]["value"] == 0
        assert by["host_syncs"]["workload"]["phase"] == "k4"
        assert by["host_syncs"]["platform"] == "cpu"
        assert by["host_syncs"]["git_sha"] == "feedfacecafe"
        assert all(r["quality"] == "complete" for r in rows)

    def test_serve_phase_error_degrades_run(self):
        rec = serve_record()
        rec["phases"]["persistent"] = {"error": "deadline share exceeded"}
        rows = ledger_mod.ingest_serve_record(rec, run_id="s1")
        assert rows and all(r["quality"] == "degraded" for r in rows)

    def test_bench_record_rows(self):
        rows = ledger_mod.ingest_bench_record(bench_record(), run_id="b1")
        by = {(r["workload"].get("phase"), r["metric"]): r for r in rows}
        assert by[("train", "tokens_per_sec")]["metric_class"] == "timing"
        assert by[("train", "tokens_per_sec")]["platform"] == "tpu"
        assert by[("train", "train_window_compiles")]["value"] == 0
        assert by[("materialize_7b", "params")]["metric_class"] == "counter"
        assert by[("driver", "bench_complete")]["value"] == 1
        assert all(r["quality"] == "complete" for r in rows)

    def test_bench_wrapper_degraded_wedge(self):
        # the r04/r05 shape: rc=0 but the inner record never got past
        # preflight — everything must land degraded
        inner = bench_record(progress="preflight-failed")
        for k in ("value", "vs_baseline", "tokens_per_sec", "mfu"):
            inner[k] = None
        wrapper = {"n": 4, "rc": 0, "tail": json.dumps(inner), "parsed": inner}
        rows = ledger_mod.ingest_bench_wrapper(wrapper, run_id="r04")
        assert rows and all(r["quality"] == "degraded" for r in rows)
        assert any(r["metric"] == "bench_rc" for r in rows)

    def test_multichip_rows(self):
        rows = ledger_mod.ingest_multichip_record(
            multichip_record(), run_id="m1"
        )
        by = {r["metric"]: r for r in rows}
        assert by["dryrun_legs"]["value"] == 2
        assert by["dryrun_ok"]["value"] == 1
        assert by["leg_comm_bytes"]["value"] == 1536  # summed by-axis
        assert by["leg_comm_bytes"]["workload"]["leg"] == "fsdp_sp"
        assert by["leg_seconds"]["metric_class"] == "timing"

    def test_flight_dump_rows(self, tmp_path):
        path = str(tmp_path / "flight.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps({"kind": "flight_header",
                                "schema": "tdx-flight-v1",
                                "reason": "bench_train", "dropped": 2}) + "\n")
            f.write(json.dumps({"kind": "step", "loss": 1.0}) + "\n")
            f.write(json.dumps({"kind": "failure", "what": "nan"}) + "\n")
            f.write(json.dumps({"kind": "rollback",
                                "restored_step": 3}) + "\n")
        rows = ledger_mod.ingest_flight_dump(path, run_id="f1")
        by = {r["metric"]: r["value"] for r in rows}
        assert by == {"flight_records": 4, "flight_dropped": 2,
                      "flight_failures": 1, "flight_rollbacks": 1}

    def test_ingest_artifact_rejects_unknown(self, tmp_path):
        p = tmp_path / "mystery.json"
        p.write_text('{"hello": 1}')
        with pytest.raises(ValueError):
            ledger_mod.ingest_artifact(str(p))

    def test_ingest_artifact_explicit_sha_beats_record_stamp(self, tmp_path):
        p = tmp_path / "rec.json"
        p.write_text(json.dumps(serve_record()))  # stamped feedfacecafe
        rows = ledger_mod.ingest_artifact(str(p), git_sha="caller0000")
        assert {r["git_sha"] for r in rows} == {"caller0000"}
        rows = ledger_mod.ingest_artifact(str(p))
        assert {r["git_sha"] for r in rows} == {"feedfacecafe"}

    def test_dirty_artifact_gets_mtime_not_commit_time(self, tmp_path):
        # an untracked/modified artifact is a FRESH run: its rows must
        # not share the committed version's timestamp identity (the
        # gate's never-your-own-baseline rule keys on (run_id, ts))
        p = tmp_path / "BENCH_SERVE_FRESH.json"
        p.write_text(json.dumps(serve_record()))
        rows = ledger_mod.ingest_artifact(str(p))
        assert rows[0]["ts"] == pytest.approx(os.path.getmtime(p), abs=1.0)


class TestBackfillRealArtifacts:
    """The committed repo-root artifacts ARE the backfill corpus — this
    pins the acceptance criterion that every family lands rows."""

    @pytest.fixture(scope="class")
    def rows(self):
        sys.path.insert(0, SCRIPTS)
        try:
            import backfill_ledger
        finally:
            sys.path.remove(SCRIPTS)
        rows, report = backfill_ledger.collect_rows(REPO)
        assert report
        return rows

    def test_all_rows_schema_valid(self, rows):
        assert rows
        assert not [e for r in rows for e in ledger_mod.validate_ledger_row(r)]

    def test_every_committed_family_lands(self, rows):
        runs = {r["run_id"] for r in rows}
        expected = {
            "BENCH_r03", "BENCH_SERVE_CPU", "KERNEL_ACCEPT_SMOKE",
            "MULTICHIP_r01", "MULTICHIP_r02", "MULTICHIP_r03",
            "MULTICHIP_r04", "MULTICHIP_r05",
        }
        assert expected <= runs, expected - runs

    def test_failed_rounds_are_degraded(self, rows):
        # BENCH_r03 is the driver round that timed out (rc != 0)
        quals = {r["quality"] for r in rows if r["run_id"] == "BENCH_r03"}
        assert quals == {"degraded"}, quals

    def test_complete_rounds_attributed_to_commits(self, rows):
        shas = {
            r["git_sha"] for r in rows if r["run_id"] == "MULTICHIP_r05"
        }
        assert shas and all(shas), (
            "backfilled rows must carry a commit sha"
        )

    def test_committed_ledger_matches_schema(self):
        path = os.path.join(REPO, "LEDGER.jsonl")
        assert os.path.exists(path), "LEDGER.jsonl must be committed"
        assert ledger_mod.validate_ledger_file(path) == []


# --------------------------------------------------------------------------
# gate semantics
# --------------------------------------------------------------------------

class TestGate:
    def test_exact_pass_and_perturbed_fail(self):
        rows = ledger_mod.ingest_serve_record(serve_record(), run_id="a")
        exp = gate_mod.build_expectations(rows)
        verdict = gate_mod.gate_rows(rows, exp, [])
        assert verdict["ok"], verdict["failures"]
        assert verdict["checked_counters"] > 0
        perturbed = ledger_mod.ingest_serve_record(
            serve_record(host_syncs=13), run_id="b"
        )
        verdict = gate_mod.gate_rows(perturbed, exp, [])
        assert not verdict["ok"]
        failed = {f["metric"] for f in verdict["failures"]}
        # the raw counter AND its derived exact ratio both trip
        assert "host_syncs" in failed and "syncs_per_token" in failed

    def test_missing_counter_row_fails(self):
        rows = ledger_mod.ingest_serve_record(serve_record(), run_id="a")
        exp = gate_mod.build_expectations(rows)
        rows_missing = [r for r in rows if r["metric"] != "host_syncs"]
        verdict = gate_mod.gate_rows(rows_missing, exp, [])
        kinds = {(f["kind"], f["metric"]) for f in verdict["failures"]}
        assert ("missing_counter", "host_syncs") in kinds

    def test_expectations_refuse_degraded_runs(self):
        rec = serve_record()
        rec["phases"]["x"] = {"error": "boom"}
        rows = ledger_mod.ingest_serve_record(rec, run_id="a")
        with pytest.raises(ValueError):
            gate_mod.build_expectations(rows)

    def test_degraded_record_fails_strict_gate(self):
        rec = serve_record()
        rec["phases"]["x"] = {"error": "boom"}
        rows = ledger_mod.ingest_serve_record(rec, run_id="a")
        verdict = gate_mod.gate_rows(rows, None, [])
        assert not verdict["ok"]
        assert any(
            f["kind"] == "degraded_input" for f in verdict["failures"]
        )

    def _timing_row(self, value, run_id, metric="decode_tokens_per_sec",
                    quality="complete"):
        return ledger_mod.make_row(
            run_id=run_id, source="bench_serve", metric=metric, value=value,
            metric_class="timing", quality=quality,
            workload={"phase": "k4"}, platform="cpu",
        )

    def test_timing_band_higher_is_better(self):
        base = [self._timing_row(100.0, "old")]
        ok = gate_mod.gate_rows([self._timing_row(90.0, "new")], None, base)
        assert ok["ok"]  # inside the 25% band
        better = gate_mod.gate_rows(
            [self._timing_row(140.0, "new")], None, base
        )
        assert better["ok"]  # improvements always pass
        bad = gate_mod.gate_rows([self._timing_row(60.0, "new")], None, base)
        assert not bad["ok"]
        f = bad["failures"][0]
        assert f["kind"] == "timing_regression"
        assert f["direction"] == "higher"
        assert f["baseline_run"] == "old"

    def test_timing_band_lower_is_better(self):
        base = [self._timing_row(1.0, "old", metric="drain_wall_s")]
        ok = gate_mod.gate_rows(
            [self._timing_row(1.2, "new", metric="drain_wall_s")], None, base
        )
        assert ok["ok"]
        bad = gate_mod.gate_rows(
            [self._timing_row(1.5, "new", metric="drain_wall_s")], None, base
        )
        assert not bad["ok"]
        assert bad["failures"][0]["direction"] == "lower"

    def test_degraded_rows_never_baseline(self):
        # degraded row is the best value; it must be ignored and the
        # complete row used instead
        base = [
            self._timing_row(1000.0, "wedged", quality="degraded"),
            self._timing_row(100.0, "good"),
        ]
        verdict = gate_mod.gate_rows([self._timing_row(90.0, "new")],
                                     None, base)
        assert verdict["ok"], verdict["failures"]
        # sanity: had the degraded row been the baseline, 90 << 750
        # would have failed the band
        assert gate_mod.gate_rows(
            [self._timing_row(90.0, "new")],
            None,
            [self._timing_row(1000.0, "wedged"),
             self._timing_row(100.0, "good")],
        )["ok"] is False

    def test_new_run_never_its_own_baseline(self):
        rows = [self._timing_row(100.0, "new")]
        verdict = gate_mod.gate_rows(rows, None, rows)
        assert verdict["checked_timings"] == 0
        assert any(s["kind"] == "no_baseline" for s in verdict["skipped"])

    def test_same_name_prior_run_IS_a_baseline(self):
        # the nightly workflow: the same artifact basename is gated
        # night after night — a PRIOR run sharing the run_id (but not
        # the timestamp) must serve as the baseline; only the run's own
        # (run_id, ts) identity is excluded
        prior = self._timing_row(100.0, "BENCH_SERVE_CPU")
        prior["ts"] = 1000.0
        new = self._timing_row(60.0, "BENCH_SERVE_CPU")
        new["ts"] = 2000.0
        verdict = gate_mod.gate_rows([new], None, [prior, dict(new)])
        assert verdict["checked_timings"] == 1
        assert not verdict["ok"]  # 60 < 100 * 0.75 — real regression caught

    def test_direction_registry(self):
        assert gate_mod.timing_direction("decode_tokens_per_sec") == "higher"
        assert gate_mod.timing_direction("mfu") == "higher"
        assert gate_mod.timing_direction("goodput") == "higher"
        assert gate_mod.timing_direction("prefix_hit_rate") == "higher"
        assert gate_mod.timing_direction("drain_wall_s") == "lower"
        assert gate_mod.timing_direction("ttft_s_p95") == "lower"
        assert gate_mod.timing_direction("peak_host_rss_gb") == "lower"

    def test_markdown_render_names_failures(self):
        rows = ledger_mod.ingest_serve_record(
            serve_record(host_syncs=13), run_id="b"
        )
        exp = gate_mod.build_expectations(
            ledger_mod.ingest_serve_record(serve_record(), run_id="a")
        )
        md = gate_mod.render_gate_markdown(gate_mod.gate_rows(rows, exp, []))
        assert "FAIL" in md and "host_syncs" in md


# --------------------------------------------------------------------------
# CLI contracts (subprocess: the nonzero-exit acceptance criterion)
# --------------------------------------------------------------------------

def _run(args, **kw):
    return subprocess.run(
        [sys.executable] + args, capture_output=True, text=True, cwd=REPO,
        timeout=120, **kw,
    )


class TestCLIs:
    @pytest.fixture(scope="class")
    def env(self, tmp_path_factory):
        d = tmp_path_factory.mktemp("perfcli")
        record = d / "record.json"
        record.write_text(json.dumps(serve_record()))
        exp = d / "expect.json"
        r = _run([
            os.path.join(SCRIPTS, "perf_gate.py"), str(record),
            "--update-expectations", str(exp),
        ])
        assert r.returncode == 0, r.stderr
        ledger = d / "ledger.jsonl"
        rows = ledger_mod.ingest_serve_record(
            serve_record(), run_id="prior", ts=1.0
        )
        ledger_mod.append_rows(str(ledger), rows)
        return {"dir": d, "record": record, "exp": exp, "ledger": ledger}

    def test_gate_pass_rc0(self, env):
        r = _run([
            os.path.join(SCRIPTS, "perf_gate.py"), str(env["record"]),
            "--expectations", str(env["exp"]),
            "--ledger", str(env["ledger"]), "--strict",
        ])
        assert r.returncode == 0, r.stderr
        verdict = json.loads(r.stdout.strip().splitlines()[-1])
        assert verdict["ok"] and verdict["schema"] == "tdx-gate-v1"
        # timing rows got real baselines from the prior ledger run
        assert verdict["checked_timings"] > 0

    def test_perturbed_counter_rc_nonzero_names_metric(self, env):
        rec = serve_record()
        rec["phases"]["k4"]["metrics"]["counters"]["decode_dispatches"] += 1
        bad = env["dir"] / "perturbed.json"
        bad.write_text(json.dumps(rec))
        r = _run([
            os.path.join(SCRIPTS, "perf_gate.py"), str(bad),
            "--expectations", str(env["exp"]),
            "--ledger", str(env["ledger"]), "--strict",
        ])
        assert r.returncode != 0
        assert "decode_dispatches" in r.stderr

    def test_gate_append_after_gating(self, env):
        led = env["dir"] / "append.jsonl"
        r = _run([
            os.path.join(SCRIPTS, "perf_gate.py"), str(env["record"]),
            "--expectations", str(env["exp"]),
            "--ledger", str(led), "--append",
        ])
        assert r.returncode == 0, r.stderr
        assert ledger_mod.read_ledger(str(led))

    def test_perf_report_trend_and_ab(self, env):
        rows = ledger_mod.ingest_serve_record(
            serve_record(host_syncs=12), run_id="later", ts=2.0
        )
        ledger_mod.append_rows(str(env["ledger"]), rows)
        r = _run([
            os.path.join(SCRIPTS, "perf_report.py"),
            "--ledger", str(env["ledger"]),
        ])
        assert r.returncode == 0, r.stderr
        assert "host_syncs" in r.stdout and "Perf trend report" in r.stdout
        r = _run([
            os.path.join(SCRIPTS, "perf_report.py"),
            "--ledger", str(env["ledger"]), "--ab", "prior", "later",
        ])
        assert r.returncode == 0, r.stderr
        assert "A/B" in r.stdout and "host_syncs" in r.stdout

    def test_check_obs_artifacts_ledger_mode(self, env):
        chk = os.path.join(SCRIPTS, "check_obs_artifacts.py")
        r = _run([chk, "--ledger", str(env["ledger"])])
        assert r.returncode == 0, r.stderr
        bad = env["dir"] / "bad.jsonl"
        bad.write_text('{"schema": "tdx-ledger-v0"}\n')
        r = _run([chk, "--ledger", str(bad)])
        assert r.returncode != 0
        assert "FAIL" in r.stderr

    def test_committed_expectations_match_committed_smoke_workload(self):
        """The nightly gates BENCH_SERVE_CPU.json (regenerated by the CI
        smoke at --requests 6 --max-new 8 --slots 2 --decode-chunk 4)
        against the committed expectations — the pinned fingerprints
        must describe exactly that invocation."""
        path = os.path.join(REPO, "expectations", "serve_cpu_smoke.json")
        assert os.path.exists(path)
        with open(path) as f:
            doc = json.load(f)
        assert gate_mod.validate_expectations(doc) == []
        fps = set(doc["counters"])
        phase_fps = {fp for fp in fps if "program=" not in fp}
        cost_fps = fps - phase_fps
        # k1 + k4 + persistent + the ISSUE 11 speculate sweep (spec0
        # baseline rides at its own geometry — the spec phases stretch
        # max_new so the self-repetition the n-gram drafter needs can
        # establish, hence their own fingerprint family) + the ISSUE 17
        # int8 --kv-quant-ab rider (kv_dtype=int8 tags its fingerprint,
        # so the quantized family never collides with the default pins)
        # + the ISSUE 19 --numerics rider (numerics=True phase pins and
        # its per-site numerics_site= digest families)
        num_fps = {fp for fp in phase_fps if "phase=numerics" in fp}
        assert len(num_fps) == 4
        assert all("numerics=True" in fp for fp in num_fps)
        assert len(phase_fps - num_fps) == 7
        kvq_fps = {fp for fp in phase_fps if "phase=kv_quant" in fp}
        assert len(kvq_fps) == 1 and "kv_dtype=int8" in next(iter(kvq_fps))
        assert any(
            "kv_dtype=int8" in fp for fp in fps if "program=serve/" in fp
        )
        for fp in fps:
            assert "requests=6" in fp
            assert "model=tiny" in fp and "num_slots=2" in fp
        spec_fps = {fp for fp in phase_fps if "speculate=" in fp}
        assert {fp.split("phase=")[1].split("|")[0] for fp in spec_fps} \
            == {"spec0", "spec2", "spec4"}
        for fp in spec_fps:
            assert "decode_mode=persistent" in fp
        for fp in fps - spec_fps:
            assert "max_new_tokens=8" in fp or "program=" in fp
        assert any("phase=persistent" in fp for fp in phase_fps)
        # cost observatory (ISSUE 8): each phase additionally pins its
        # programs' XLA HLO-analysis counts under program-tagged
        # fingerprints — and ONLY those (buffer-assignment sizes stay
        # out of the pins per gate.DEFAULT_COUNTER_EXCLUDE)
        assert cost_fps and all("program=serve/" in fp for fp in cost_fps)
        for fp in cost_fps:
            assert set(doc["counters"][fp]) <= {
                "cost_flops", "cost_bytes_accessed", "cost_transcendentals"
            }


class TestRecordStamp:
    def test_stamp_has_schema_and_sha(self):
        stamp = ledger_mod.record_stamp()
        assert stamp["record_schema"] == "tdx-record-v1"
        # in this checkout git is available, so the sha must resolve
        assert stamp["git_sha"]

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("TDX_GIT_SHA", "deadbeef")
        assert ledger_mod.git_sha() == "deadbeef"

    def test_append_record_rows_never_raises(self, tmp_path, monkeypatch):
        monkeypatch.setenv(
            "TDX_LEDGER_PATH", str(tmp_path / "nodir" / "x.jsonl")
        )
        # unwritable path: must swallow and return 0, not raise
        assert ledger_mod.append_record_rows(
            serve_record(), source="bench_serve"
        ) == 0

    def test_append_record_rows_disabled(self, tmp_path, monkeypatch):
        path = tmp_path / "led.jsonl"
        monkeypatch.setenv("TDX_LEDGER_PATH", str(path))
        monkeypatch.setenv("TDX_LEDGER", "0")
        assert ledger_mod.append_record_rows(
            serve_record(), source="bench_serve"
        ) == 0
        assert not path.exists()
        monkeypatch.delenv("TDX_LEDGER")
        assert ledger_mod.append_record_rows(
            serve_record(), source="bench_serve"
        ) > 0
        assert path.exists()
