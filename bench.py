"""Benchmark: deferred_init -> materialize wall-clock (BASELINE.json metric)
plus single-chip training throughput (tokens/sec + MFU).

Phase 1 — north-star config (BASELINE.json config 5): Llama-2-7B through
the full flagship pipeline on the attached accelerator — storage-less
deferred construction, then eager on-device replay materialization (bf16,
6.74B params).  ``vs_baseline`` is the north-star budget ratio: target is
<60 s (and <32 GB host RAM); >1.0 means faster than budget.

Phase 2 — the other half of the BASELINE metric ("FSDP step tokens/sec/
chip"): a 1B-class Llama train step (flash attention, AnyPrecisionAdamW,
bf16, remat off — batch 2x2048 activations fit HBM; TDX_BENCH_REMAT=1
for shapes that don't) timed over a multi-second window.  Reported as
``tokens_per_sec`` and model-FLOPs ``mfu`` in the same JSON line; ``mfu``
is null, with the reason beside it, on a device kind that has no peak in
``utils.benchmarks.PEAK_BF16_FLOPS`` (a CPU rehearsal, say).

Every phase record names the device it ran on (``platform``,
``device_kind``, ``device_count``).  The device is whatever JAX finds: on
the chip machine the TPU; for a rehearsal here, ``JAX_PLATFORMS=cpu``
(plus ``TDX_BENCH_MODEL=tiny TDX_BENCH_TRAIN_MODEL=tiny TDX_BENCH_SEQ=64``).

Every phase runs in its own subprocess — each nearly fills the 16 GB chip
and needs a fresh HBM arena, and a chip belongs to one process at a time,
so the parent never imports jax — under a per-phase budget carved from a
global deadline (``TDX_BENCH_DEADLINE``, default 1500 s).  A parseable
JSON record line is emitted after EVERY phase (flushed); the final line is
the full record.  A phase that fails or times out is recorded as
``{"failed": ...}`` and the run exits non-zero.
"""

from __future__ import annotations

import functools
import json
import math
import os
import resource
import time

def _phase_setup() -> dict:
    """Place the compile cache (before the first jit) and return what
    every phase record says about where it ran."""
    import jax

    from torchdistx_tpu.utils.compile_cache import use_compile_cache

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "compile_cache": use_compile_cache(),
    }


def _train_throughput():
    device_fields = _phase_setup()
    import time as _time

    import numpy as np

    if os.environ.get("TDX_BENCH_ZERO2", "0") == "1":
        import jax

        if jax.device_count() < 2:
            return {
                "skipped": "zero2 needs >=2 devices",
                "detail": f"{jax.device_count()} device(s) visible; the "
                "ZeRO-2 A/B only runs on multi-device meshes (the CPU "
                "smoke forces 8 virtual devices via XLA_FLAGS)",
            }

    from torchdistx_tpu.utils.benchmarks import (
        build_train_workload,
        peak_bf16_flops,
        warm_to_steady_state,
    )

    # no peak on record for this device kind -> no mfu, and the record
    # says why; never a default peak
    try:
        _PEAK, mfu_error = peak_bf16_flops(device_fields["device_kind"]), None
    except LookupError as e:
        _PEAK, mfu_error = None, str(e)

    from torchdistx_tpu.obs import RecompileWatcher, recompile_scope
    from torchdistx_tpu.obs.flight import get_flight_recorder

    flight = get_flight_recorder()
    t_phase0 = _time.perf_counter()
    n_steps = 20
    w = build_train_workload(n_steps)
    if w.get("zero2"):
        # the A/B verdicts, checked where the numbers are born — a
        # failed assert fails this phase, and with it the run
        dp = w["zero2_dp"]
        assert w["optimizer_bytes_per_device"] < w["optimizer_bytes"], (
            "zero2 did not shrink optimizer bytes/device: "
            f"{w['optimizer_bytes_per_device']} of {w['optimizer_bytes']}"
        )
        pinned = w["zero2_participating_bytes"] * (dp - 1) // dp
        assert w["zero2_step_wire_bytes"] == pinned, (
            "zero2 step wire bytes off the ring closed form: "
            f"{w['zero2_step_wire_bytes']} != {pinned}"
        )
    run, carry = w["run"], w["carry"]
    flight.record(
        "bench_train_start", model=w["name"], steps=n_steps,
        batch=w["batch"], seq=w["seq"],
    )

    # warm to the layout fixpoint — a single warm call would time the
    # donated-carry recompile, round-2's measurement bug (see
    # utils.benchmarks.warm_to_steady_state).  The recompile watcher
    # turns that from a timing inference into counters in the record:
    # warm-up compiles under "warmup", and the timed window's compiles
    # under "timed_window" (expected ZERO when warm_converged).
    # under TDX_NUMERICS=1 the workload's aux is (losses, digests) — the
    # digests ride the SAME scanned program (zero extra dispatches) and
    # the record embeds the book below
    num_on = bool(w.get("numerics"))

    def _losses(aux):
        return aux[0] if num_on else aux

    watcher = RecompileWatcher()
    carry, warm_times, warm_converged = warm_to_steady_state(
        run,
        carry,
        sync=lambda aux: float(np.asarray(_losses(aux)[-1])),
        watcher=watcher,
        label="warmup",
    )

    t0 = _time.perf_counter()
    with recompile_scope("timed_window"):
        carry, aux = run(carry)
        # forces the whole chain
        final_loss = float(np.asarray(_losses(aux)[-1]))
    dt = _time.perf_counter() - t0

    numerics_book = None
    if num_on:
        try:
            import jax

            from torchdistx_tpu.obs.numerics import NumericsBook

            book = NumericsBook()
            book.update_tree(jax.device_get(aux[1]))
            numerics_book = book.to_json()
        except Exception as e:  # telemetry must not kill the bench
            numerics_book = {"error": f"{type(e).__name__}: {e}"[:200]}

    toks = n_steps * w["batch"] * w["seq"]
    tokens_per_sec = toks / dt
    mfu = (
        tokens_per_sec * w["flops_per_token"] / _PEAK if _PEAK else None
    )

    # cost observatory (obs.cost): card the train program AFTER the
    # timed window (the card's own compile must not pollute it), then
    # attribute the analytic FLOP model against XLA's count and report
    # the timed span's MFU from BOTH — the formula-vs-compiler check
    # that would have caught the round-3 ~0.87x-of-formula finding as a
    # number instead of a trace-reading session.  TDX_COST_CARDS=0
    # skips (one extra whole-program compile).
    cost_card = None
    mfu_xla = None
    from torchdistx_tpu.obs.cost import compute_cost_card, force_disabled

    if not force_disabled():
        try:
            card = compute_cost_card(
                run, carry, name="train/step",
                analytic_flops=float(w["flops_per_token"]) * toks,
            )
            cost_card = card.to_json()
            if card.flops and _PEAK:
                # the whole `run` program is n_steps steps: per-span MFU
                # over the same dt the analytic mfu used
                mfu_xla = round(card.flops / (dt * _PEAK), 4)
        except Exception as e:
            cost_card = {"error": f"{type(e).__name__}: {e}"[:200]}
    # goodput: the timed window's productive fraction of the phase —
    # everything else is warmup/compile (the donated-carry tax made
    # visible as a ratio, not just a warm-call list)
    phase_s = _time.perf_counter() - t_phase0
    goodput = dt / phase_s if phase_s > 0 else None
    flight.record(
        "bench_train_end",
        tokens_per_sec=round(tokens_per_sec, 1),
        mfu=round(mfu, 4) if mfu is not None else None,
        goodput=round(goodput, 4) if goodput else None,
        warm_converged=warm_converged,
        compiles=watcher.snapshot()["compiles_total"],
    )
    return {
        # crash-dump telemetry: the black box for THIS phase subprocess
        # (always written; the parent embeds the path in the record)
        "flight_dump": flight.dump(reason="bench_train"),
        "goodput": round(goodput, 4) if goodput else None,
        "train_model": w["name"],
        "train_params": w["n_params"],
        "train_batch": w["batch"],
        "train_seq": w["seq"],
        "train_steps_timed": n_steps,
        "train_warm_calls_s": [round(t, 2) for t in warm_times],
        # False would mean the timed window may still contain a recompile
        "train_warm_converged": warm_converged,
        # the watcher's counters back that flag with numbers: compiles
        # attributed to warm-up vs the timed window (window must be 0)
        "train_recompile": watcher.snapshot(),
        # the card + the XLA-counted span MFU ride next to the analytic
        # mfu; their ratio is cost_card["flop_attribution"]
        "train_cost_card": cost_card,
        "mfu_xla": mfu_xla,
        "train_window_s": round(dt, 3),
        "train_final_loss": round(final_loss, 4)
        if math.isfinite(final_loss)
        else None,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": round(mfu, 4) if mfu is not None else None,
        **({"mfu_error": mfu_error} if mfu_error else {}),
        **device_fields,
        "flash_attention": True,
        "remat": w["remat"],  # what the workload actually built
        "remat_policy": w["remat_policy"],
        "optimizer": w["optimizer"],
        "fused_ce": w["fused_ce"],
        "zero2": w["zero2"],
        # digest book (tdx-numerics-v1) only under TDX_NUMERICS=1, so
        # default-run records stay byte-stable
        **({"numerics_book": numerics_book} if numerics_book else {}),
        # plan/byte fields only present on the zero2 arm
        **{
            k: w[k]
            for k in (
                "plan", "zero2_dp", "optimizer_bytes",
                "optimizer_bytes_per_device", "zero2_participating_bytes",
                "zero2_step_wire_bytes",
            )
            if k in w
        },
    }


def _materialize_7b(replay_mode: str) -> dict:
    device_fields = _phase_setup()
    import jax

    import torchdistx_tpu as tdx
    from torchdistx_tpu._graph import RecordingSession
    from torchdistx_tpu.models import Llama

    RecordingSession.replay_mode = replay_mode
    bench_model = os.environ.get("TDX_BENCH_MODEL", "llama2_7b")  # tiny for smoke tests
    t0 = time.time()
    tdx.manual_seed(0)
    model = tdx.deferred_init(Llama.from_name, bench_model)
    t_defer = time.time() - t0
    n_params = model.num_params()

    t0 = time.time()
    tdx.materialize_module(model)
    jax.block_until_ready([p for _, p in model.named_parameters()])
    t_mat = time.time() - t0
    # the machine-checkable memory plan (obs.memory): sharding-audit
    # summary + device/host watermark for the 7B materialization
    from torchdistx_tpu.obs import memory_report

    mem = memory_report(model)
    return {
        "replay_mode": replay_mode,
        "deferred_init_s": round(t_defer, 3),
        "materialize_s": round(t_mat, 3),
        "total_s": round(t_defer + t_mat, 3),
        "params": int(n_params),
        "peak_host_rss_gb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 3
        ),
        "memory": mem,
        "device": str(jax.devices()[0]),
        **device_fields,
    }


def _run_phase(
    arg: str, timeout_s: float, *, script: str = None, env: dict = None
) -> dict:
    """Run one bench phase in a subprocess and return its record.

    A phase that fails, times out, or prints no JSON comes back as
    ``{"failed": ..., "detail": ...}``: the bench goes on to the next
    phase so one fault does not hide the others, and ``main`` exits
    non-zero if any record carries ``failed``.  ``{"skipped": ...}`` is
    what a phase itself returns for an arm that does not apply (ZeRO-2
    on one device); it is not a failure.

    ``script``/``env`` run a sibling driver (the kernel-acceptance
    sweep) under the same contract.
    """
    import subprocess
    import sys

    name = arg or script
    if timeout_s <= 0:
        return {"failed": "deadline exhausted",
                "detail": f"no budget left for phase {name}"}
    cmd = [sys.executable, script or __file__] + ([arg] if arg else [])
    try:
        proc = subprocess.run(
            cmd,
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=env,
        )
    except subprocess.TimeoutExpired:
        return {
            "failed": "timeout",
            "detail": f"phase {name} ran past {timeout_s:.0f}s; "
            "subprocess killed",
        }
    if proc.returncode != 0:
        tail = (proc.stdout[-1000:] + proc.stderr[-1000:]).strip()
        return {"failed": f"phase {name} rc={proc.returncode}",
                "detail": tail[-500:]}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (json.JSONDecodeError, IndexError):
        return {"failed": f"phase {name} produced no JSON",
                "detail": proc.stdout[-500:]}


def _run_kernel_sweep(timeout_s: float) -> dict:
    """Final bench phase: the kernel acceptance sweep
    (scripts/verify_kernels_onchip.py), under the same ``_run_phase``
    contract.  Artifact semantics (see the sweep's docstring): compiled
    runs write KERNEL_ACCEPT.json, non-TPU runs divert to
    KERNEL_ACCEPT_SMOKE.json."""
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "scripts", "verify_kernels_onchip.py")
    env = dict(os.environ, TDX_VERIFY_DEADLINE=str(int(timeout_s - 5)))
    return _run_phase("", timeout_s, script=script, env=env)


def _ledger():
    """Load ``torchdistx_tpu/obs/ledger.py`` WITHOUT importing the
    package: the supervising parent never touches jax or the native
    build, and the ledger module is stdlib-only by design.  Memoized in
    ``sys.modules`` so per-emit calls share one module instance (and
    its git-sha cache: one subprocess per run, not per phase emit)."""
    import importlib.util
    import sys

    mod = sys.modules.get("_tdx_ledger")
    if mod is not None:
        return mod
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "torchdistx_tpu", "obs", "ledger.py")
    spec = importlib.util.spec_from_file_location("_tdx_ledger", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules["_tdx_ledger"] = mod
    return mod


def _record(train: dict, eager: dict, chunked: dict,
            progress: str, kernels: dict, train_fused: dict,
            train_zero2: dict) -> dict:
    """Assemble the (always-parseable) bench record from whatever ran."""
    train = dict(train)
    eager_ok = "total_s" in eager
    total = eager.get("total_s")
    return (
        {
            "metric": "deferred_init_materialize_llama2_7b_wall_s",
            # commit + schema attribution (perf-sentinel satellite: runs
            # were previously unattributable to commits)
            **_ledger().record_stamp(),
            "value": round(total, 3) if eager_ok else None,
            "unit": "s",
            "vs_baseline": round(60.0 / total, 3) if eager_ok else None,
            "tokens_per_sec": train.pop("tokens_per_sec", None),
            "mfu": train.pop("mfu", None),
            # training-telemetry fields (ISSUE 5): productive fraction of
            # the train phase + the phase's flight-recorder dump path
            "goodput": train.pop("goodput", None),
            "flight_dump": train.pop("flight_dump", None),
            "extra": {
                "progress": progress,
                "kernel_acceptance": kernels,
                # fused-CE A/B leg, trimmed to its verdict fields
                "train_fused_ce": {
                    k: train_fused[k]
                    for k in ("tokens_per_sec", "mfu", "train_final_loss",
                              "train_warm_converged", "fused_ce",
                              "train_model", "skipped", "failed", "detail")
                    if k in train_fused
                },
                # ZeRO-2 A/B leg (plan-sharded optimizer state over a
                # dp mesh), trimmed to its verdict + pinned-byte fields
                "train_zero2": {
                    k: train_zero2[k]
                    for k in ("tokens_per_sec", "mfu", "train_final_loss",
                              "train_warm_converged", "zero2", "plan",
                              "zero2_dp", "optimizer_bytes",
                              "optimizer_bytes_per_device",
                              "zero2_participating_bytes",
                              "zero2_step_wire_bytes", "train_model",
                              "skipped", "failed", "detail")
                    if k in train_zero2
                },
                "deferred_init_s": eager.get("deferred_init_s"),
                "materialize_s": eager.get("materialize_s"),
                "params": eager.get("params"),
                "peak_host_rss_gb": eager.get("peak_host_rss_gb"),
                "memory": eager.get("memory"),
                "north_star": "<60s, <32GB host RAM (BASELINE.json cfg 5)",
                "device": eager.get("device"),
                "materialize_eager_status": ("ok" if eager_ok else eager),
                "materialize_chunked": chunked,
                "train_status": (
                    "ok" if "train_window_s" in train
                    else {k: train.pop(k)
                          for k in ("skipped", "failed", "detail")
                          if k in train}
                ),
                **train,
            },
        }
    )


def main() -> int:
    # Global wall-clock deadline: every phase budget is carved from what
    # remains, so the bench always terminates inside its caller's window.
    deadline = time.monotonic() + float(
        os.environ.get("TDX_BENCH_DEADLINE", "1500")
    )

    def left() -> float:
        return deadline - time.monotonic()

    pending = {"skipped": "not reached"}
    phases = {
        name: dict(pending)
        for name in ("train", "eager", "chunked", "kernels", "train_fused",
                     "train_zero2")
    }

    def emit(progress):
        # one full parseable record per phase boundary; last line wins
        rec = _record(phases["train"], phases["eager"], phases["chunked"],
                      progress, phases["kernels"], phases["train_fused"],
                      phases["train_zero2"])
        print(json.dumps(rec), flush=True)
        return rec

    emit("started")

    # Every phase runs in its own process: each nearly fills the 16 GB
    # chip and needs a fresh HBM arena.  A record line is emitted after
    # each phase.  The kernel-acceptance sweep holds a RESERVE carved out
    # of the earlier phases' budgets (degrading the chunked A/B first):
    # the phase caps alone (700+400+400+450+450+450) overrun a 1500 s
    # deadline, and without the reserve slow compiles would starve it.
    sweep_reserve = min(350.0, left() * 0.25)
    phases["train"] = _run_phase(
        "--train-phase", min(700.0, left() - sweep_reserve - 150))
    emit("train-done")

    phases["eager"] = _run_phase(
        "--materialize-phase=eager", min(400.0, left() - sweep_reserve - 50))
    emit("materialize-eager-done")

    # A/B: chunked replay batches dispatches (one per compiled chunk) —
    # measured alongside the default so the trade is always on record
    phases["chunked"] = _run_phase(
        "--materialize-phase=chunked", min(400.0, left() - sweep_reserve))
    emit("materialize-chunked-done")

    phases["kernels"] = _run_kernel_sweep(min(450.0, left() - 100))
    emit("kernel-sweep-done")

    # Fused-CE train A/B: the same train phase with the fused LM-head
    # loss (ops/fused_ce.py).
    phases["train_fused"] = _run_phase(
        "--train-phase", min(450.0, left()),
        env=dict(os.environ, TDX_BENCH_FUSED_CE="1"),
    )
    emit("train-fused-done")

    # ZeRO-2 train A/B: the same train phase with the weight update
    # sharded over a dp mesh spanning every visible device
    # (parallel/plan.py).  The child asserts the verdict itself
    # (optimizer bytes/device strictly drop; step wire bytes pinned to
    # the ring closed form) and returns {"skipped": ...} on one device;
    # a CPU rehearsal gets 8 virtual devices so the A/B runs there.
    zenv = dict(os.environ, TDX_BENCH_ZERO2="1")
    if zenv.get("JAX_PLATFORMS") == "cpu":
        zenv["XLA_FLAGS"] = (
            zenv.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    phases["train_zero2"] = _run_phase(
        "--train-phase", min(450.0, left()), env=zenv)
    rec = emit("complete")
    # perf-sentinel hook: the finished record lands in LEDGER.jsonl as
    # normalized per-metric rows (TDX_LEDGER=0 disables)
    _ledger().append_record_rows(rec, source="bench")
    failed = {k: v["failed"] for k, v in phases.items() if "failed" in v}
    if failed:
        import sys

        print(f"bench: phases failed: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    import sys

    if "--train-phase" in sys.argv:
        print(json.dumps(_train_throughput()))
    elif any(a.startswith("--materialize-phase=") for a in sys.argv):
        mode = next(
            a.split("=", 1)[1]
            for a in sys.argv
            if a.startswith("--materialize-phase=")
        )
        print(json.dumps(_materialize_7b(mode)))
    else:
        sys.exit(main())
