"""On-chip acceptance sweep for every pallas flash-attention configuration.

Purpose: the pytest suite runs the kernels in interpret mode only
(tests/conftest.py forces the CPU platform), so compiled Mosaic behavior —
VMEM scratch sizing, output-block revisiting, the bucket-bias select
chains — is exactly what the suite cannot catch.  This script runs every
kernel configuration (causal x bias x table x window x GQA x shape class)
COMPILED on the attached TPU and diffs each against the independent jnp
reference (`ops.attention.multihead_attention` and local biased variants).
The suite's interpret-mode parity tests already pin interpret == reference,
so compiled == reference here closes compiled == interpret transitively.

Process layout (a chip belongs to one process at a time, so the parent
never imports jax):

- a device probe child reports the platform the case children will see;
- cases are grouped into a few phase subprocesses, one after another;
  each case prints ONE flushed JSON line, and the parent harvests
  partial stdout even when it must kill a phase at its budget;
- everything runs under a global deadline (TDX_VERIFY_DEADLINE, default
  1200 s) and the cumulative record is rewritten after every phase;
- the exit code is non-zero unless every defined case ran and passed.

Case order is by evidentiary value: the flagship causal path first, then
the round-4 features that have never run compiled (window, bias + dbias,
bucket table + dtable), then large-shape stress.

Artifact honesty: KERNEL_ACCEPT.json is reserved for COMPILED evidence —
it is only written when the probed device platform is "tpu" (the same
predicate the kernels use to pick Mosaic over interpret mode).  Any other
platform writes KERNEL_ACCEPT_SMOKE.json instead, with
``"mode": "interpret-smoke"`` and a distinct ``metric``, so a smoke run
can never masquerade as — or clobber — the on-chip acceptance record.

The decode-attention families (ops/decode_attention.py) have no case here
yet: their compiled acceptance is tests/test_chip_compile.py (Mosaic
accepts them) and chip_smoke.py (they run and agree with the jnp path).

Smoke (harness check, interpret mode, no TPU):
    JAX_PLATFORMS=cpu python scripts/verify_kernels_onchip.py
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time
import zlib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ACCEPT_PATH = os.path.join(REPO, "KERNEL_ACCEPT.json")
SMOKE_PATH = os.path.join(REPO, "KERNEL_ACCEPT_SMOKE.json")
if REPO not in sys.path:  # children are launched by script path
    sys.path.insert(0, REPO)

# (name, phase, spec) — spec drives one fwd+bwd parity check
CASES = [
    # --- core: the flagship train/decode paths ---
    ("causal_mha_1024", "core",
     dict(b=2, sq=1024, skv=1024, hq=8, hkv=8, d=64, causal=True)),
    ("causal_gqa_1024", "core",
     dict(b=2, sq=1024, skv=1024, hq=8, hkv=2, d=64, causal=True)),
    ("noncausal_512", "core",
     dict(b=2, sq=512, skv=512, hq=4, hkv=4, d=64, causal=False)),
    ("decode_cross_256_1024", "core",
     dict(b=1, sq=256, skv=1024, hq=8, hkv=8, d=64, causal=True)),
    ("oddlen_384_blockshrink", "core",
     dict(b=2, sq=384, skv=384, hq=4, hkv=4, d=64, causal=True)),
    ("causal_f32_512", "core",
     dict(b=1, sq=512, skv=512, hq=4, hkv=4, d=64, causal=True,
          dtype="float32")),
    # --- features: round-4 paths never run compiled ---
    ("window_256_of_1024", "features",
     dict(b=2, sq=1024, skv=1024, hq=4, hkv=4, d=64, causal=True,
          window=256)),
    ("window_gqa_128", "features",
     dict(b=1, sq=1024, skv=1024, hq=8, hkv=2, d=64, causal=True,
          window=128)),
    ("bias_noncausal_512", "features",
     dict(b=2, sq=512, skv=512, hq=4, hkv=4, d=64, causal=False,
          bias=True)),
    ("bias_causal_512", "features",
     dict(b=2, sq=512, skv=512, hq=4, hkv=4, d=64, causal=True,
          bias=True)),
    ("bucket_table_enc_512", "features",
     dict(b=2, sq=512, skv=512, hq=4, hkv=4, d=64, causal=False,
          table=True, bidirectional=True)),
    ("bucket_table_dec_512", "features",
     dict(b=2, sq=512, skv=512, hq=4, hkv=4, d=64, causal=True,
          table=True, bidirectional=False)),
    # --- stress: multi-block grids at training scale ---
    ("causal_mha_4096", "stress",
     dict(b=1, sq=4096, skv=4096, hq=8, hkv=8, d=128, causal=True)),
    ("window_1024_of_4096", "stress",
     dict(b=1, sq=4096, skv=4096, hq=8, hkv=2, d=128, causal=True,
          window=1024)),
    ("causal_8192_fwd_only", "stress",
     dict(b=1, sq=8192, skv=8192, hq=4, hkv=4, d=128, causal=True,
          fwd_only=True)),
    # --- fused LM-head cross-entropy (ops/fused_ce.py) ---
    ("fused_ce_small", "fusedce",
     dict(kind="fused_ce", n=512, d=256, v=2048)),
    ("fused_ce_oddvocab", "fusedce",
     dict(kind="fused_ce", n=384, d=128, v=1000)),
    ("fused_ce_bench_shape", "fusedce",
     dict(kind="fused_ce", n=4096, d=2048, v=32000, dtype="bfloat16")),
]

PHASES = ["core", "features", "stress", "fusedce"]


def _probe() -> dict:
    """Which device the case children will run on (in a child: the
    parent must not hold the chip)."""
    import jax

    dev = jax.devices()[0]
    return {"ok": True, "device": str(dev), "platform": dev.platform,
            "device_kind": dev.device_kind}


def _ref_attention(q, k, v, *, causal, bias=None, window=None):
    """Independent jnp reference: einsum + f32 softmax (+ additive bias).

    Matches `ops.attention.multihead_attention` math; biased variant kept
    local so this script never depends on the code under test beyond the
    kernel entry point itself."""
    import jax
    import jax.numpy as jnp

    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if hq != hkv:
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias[None].astype(jnp.float32)
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        if window is not None:
            mask = mask & jnp.triu(
                jnp.ones((sq, skv), bool), k=skv - sq - (window - 1)
            )
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _max_rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    denom = np.max(np.abs(b)) + 1e-6
    return float(np.max(np.abs(a - b)) / denom)


def _run_fused_ce_case(name: str, spec: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from torchdistx_tpu.ops.fused_ce import fused_linear_cross_entropy

    dtype = jnp.dtype(spec.get("dtype", "float32"))
    n, d, v = spec["n"], spec["d"], spec["v"]
    seed = zlib.crc32(name.encode())
    k = jax.random.split(jax.random.PRNGKey(seed % (2**31)), 3)  # tdx-lint: disable=TDX102 -- name-derived verification inputs, stable across processes; not parameter init
    x = jax.random.normal(k[0], (n, d), dtype)
    w = jax.random.normal(k[1], (v, d), dtype) * 0.1
    y = jax.random.randint(k[2], (n,), 0, v)

    def ref(x, w):
        logits = jnp.einsum("nd,vd->nv", x, w).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
        )

    rec = {"case": name, "spec": spec, "dtype": str(dtype)}
    t0 = time.time()
    lf = float(jax.block_until_ready(
        jax.jit(lambda x, w: fused_linear_cross_entropy(x, w, y))(x, w)
    ))
    rec["fwd_compile_run_s"] = round(time.time() - t0, 2)
    lr = float(jax.jit(ref)(x, w))
    rec["fwd_max_rel_err"] = abs(lf - lr) / (abs(lr) + 1e-8)

    t0 = time.time()
    gk = jax.block_until_ready(jax.jit(jax.grad(
        lambda x, w: fused_linear_cross_entropy(x, w, y), argnums=(0, 1)
    ))(x, w))
    rec["bwd_compile_run_s"] = round(time.time() - t0, 2)
    gr = jax.jit(jax.grad(ref, argnums=(0, 1)))(x, w)
    for gname, a_, b_ in zip(["dx", "dw"], gk, gr):
        rec[f"{gname}_max_rel_err"] = _max_rel_err(a_, b_)

    tol = 2e-2
    errs = {k_: v_ for k_, v_ in rec.items() if k_.endswith("_max_rel_err")}
    rec["ok"] = all(e <= tol for e in errs.values())
    rec["tol"] = tol
    return rec


def _run_case(name: str, spec: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from torchdistx_tpu.ops.flash_attention import (
        flash_attention,
        rel_pos_bucket,
    )

    dtype = jnp.dtype(spec.get("dtype", "bfloat16"))
    b, sq, skv = spec["b"], spec["sq"], spec["skv"]
    hq, hkv, d = spec["hq"], spec["hkv"], spec["d"]
    causal = spec["causal"]
    window = spec.get("window")
    buckets, max_dist = 32, 128
    bidirectional = spec.get("bidirectional", False)

    seed = zlib.crc32(name.encode())  # stable across processes/runs
    keys = jax.random.split(jax.random.PRNGKey(seed % (2**31)), 6)  # tdx-lint: disable=TDX102 -- name-derived verification inputs, stable across processes; not parameter init
    q = jax.random.normal(keys[0], (b, sq, hq, d), dtype)
    k = jax.random.normal(keys[1], (b, skv, hkv, d), dtype)
    v = jax.random.normal(keys[2], (b, skv, hkv, d), dtype)
    w = jax.random.normal(keys[3], (b, sq, hq, d), dtype)  # cotangent probe

    bias = table = None
    if spec.get("bias"):
        bias = 0.5 * jax.random.normal(keys[4], (hq, sq, skv), jnp.float32)
    if spec.get("table"):
        table = 0.5 * jax.random.normal(keys[4], (hq, buckets), jnp.float32)

    def kernel_fn(q, k, v, bias, table):
        return flash_attention(
            q, k, v, causal=causal, bias=bias, window=window,
            rel_bias_table=table, rel_bias_buckets=buckets,
            rel_bias_max_dist=max_dist,
            rel_bias_bidirectional=bidirectional,
        )

    def ref_fn(q, k, v, bias, table):
        if table is not None:
            rel = (
                jnp.arange(skv)[None, :] - jnp.arange(sq)[:, None]
            )
            idx = rel_pos_bucket(
                rel, bidirectional=bidirectional, buckets=buckets,
                max_dist=max_dist,
            )
            bias = table[:, idx]  # (H, Sq, Skv)
        return _ref_attention(
            q, k, v, causal=causal, bias=bias, window=window
        )

    rec = {"case": name, "spec": spec, "dtype": str(dtype)}
    t0 = time.time()
    out_k = jax.block_until_ready(
        jax.jit(kernel_fn)(q, k, v, bias, table)
    )
    rec["fwd_compile_run_s"] = round(time.time() - t0, 2)
    out_r = jax.block_until_ready(jax.jit(ref_fn)(q, k, v, bias, table))
    rec["fwd_max_rel_err"] = _max_rel_err(out_k, out_r)

    if not spec.get("fwd_only"):
        def loss(fn):
            def f(q, k, v, bias, table):
                return jnp.sum(
                    fn(q, k, v, bias, table).astype(jnp.float32)
                    * w.astype(jnp.float32)
                )
            return f

        argnums = [0, 1, 2]
        grad_names = ["dq", "dk", "dv"]
        if bias is not None:
            argnums.append(3)
            grad_names.append("dbias")
        if table is not None:
            argnums.append(4)
            grad_names.append("dtable")
        t0 = time.time()
        gk = jax.block_until_ready(
            jax.jit(jax.grad(loss(kernel_fn), argnums=tuple(argnums)))(
                q, k, v, bias, table
            )
        )
        rec["bwd_compile_run_s"] = round(time.time() - t0, 2)
        gr = jax.block_until_ready(
            jax.jit(jax.grad(loss(ref_fn), argnums=tuple(argnums)))(
                q, k, v, bias, table
            )
        )
        for gname, a_, b_ in zip(grad_names, gk, gr):
            rec[f"{gname}_max_rel_err"] = _max_rel_err(a_, b_)

    # bf16 inputs with f32 kernel accumulation: errors land ~1e-3;
    # 2e-2 is the alarm threshold, not the expectation
    tol = 2e-2
    errs = {k_: v_ for k_, v_ in rec.items() if k_.endswith("_max_rel_err")}
    rec["ok"] = all(e <= tol for e in errs.values())
    rec["tol"] = tol
    return rec


def _phase_main(phase: str) -> None:
    from torchdistx_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    for name, ph, spec in CASES:
        if ph != phase:
            continue
        try:
            runner = (
                _run_fused_ce_case
                if spec.get("kind") == "fused_ce"
                else _run_case
            )
            rec = runner(name, spec)
        except Exception as e:  # keep sweeping: one bad case != no record
            rec = {"case": name, "spec": spec, "ok": False,
                   "error": f"{type(e).__name__}: {e}"[:500]}
        print(json.dumps(rec), flush=True)


def _harvest(stdout: str) -> list:
    recs = []
    for line in (stdout or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                recs.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return recs


def _run_phase_subprocess(arg: str, timeout_s: float) -> tuple:
    """Returns (records, status). Harvests partial output on timeout."""
    if timeout_s <= 5:
        return [], "skipped: deadline exhausted"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), arg],
            capture_output=True, text=True, timeout=timeout_s,
        )
    except subprocess.TimeoutExpired as e:
        out = e.stdout
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        return _harvest(out), (
            f"killed: exceeded its {timeout_s:.0f}s budget; partial "
            "records harvested"
        )
    recs = _harvest(proc.stdout)
    if proc.returncode != 0:
        tail = (proc.stdout[-300:] + proc.stderr[-300:]).strip()
        return recs, f"rc={proc.returncode}: {tail[-300:]}"
    return recs, "ok"


def _write_record(probe, phase_status, cases, progress, path, mode):
    """Emit the cumulative record: summary line to stdout always; the
    durable file only when ``path`` is set (``None`` = print-only, used
    for provisional/degraded states that must not clobber a prior
    compiled artifact — parents harvest stdout either way)."""
    n_ok = sum(1 for c in cases if c.get("ok"))
    # standalone-load the (stdlib-only) ledger module: the supervising
    # parent must not import the package (jax + native build); memoized
    # in sys.modules so per-phase record writes share one instance
    import importlib.util

    _ledger = sys.modules.get("_tdx_ledger")
    if _ledger is None:
        spec = importlib.util.spec_from_file_location(
            "_tdx_ledger",
            os.path.join(REPO, "torchdistx_tpu", "obs", "ledger.py"),
        )
        _ledger = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_ledger)
        sys.modules["_tdx_ledger"] = _ledger

    record = {
        # interpret-mode smoke runs get a distinct metric name so no
        # consumer can mistake them for compiled-Mosaic acceptance
        "metric": ("flash_kernel_onchip_acceptance"
                   if mode == "compiled-mosaic"
                   else "flash_kernel_interpret_smoke"),
        **_ledger.record_stamp(),
        "mode": mode,
        "progress": progress,
        # the device probe; the key keeps its ledger-schema name
        "preflight": probe,
        "phase_status": phase_status,
        "cases_total_defined": len(CASES),
        "cases_run": len(cases),
        "cases_ok": n_ok,
        # the sweep's promise is the WHOLE surface: partial runs never
        # report aggregate acceptance
        "all_ok": n_ok == len(CASES),
        "cases": cases,
    }
    if path is not None:
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
    print(json.dumps({k: v for k, v in record.items() if k != "cases"}),
          flush=True)


def main() -> int:
    deadline = time.monotonic() + float(
        os.environ.get("TDX_VERIFY_DEADLINE", "1200")
    )

    def left() -> float:
        return deadline - time.monotonic()

    probe_recs, probe_status = _run_phase_subprocess(
        "--probe", min(120.0, left())
    )
    if not probe_recs or not probe_recs[-1].get("ok"):
        print(f"verify_kernels_onchip: device probe failed: {probe_status}",
              file=sys.stderr)
        return 2
    probe = probe_recs[-1]
    compiled = probe["platform"] == "tpu"
    out_path = ACCEPT_PATH if compiled else SMOKE_PATH
    mode = "compiled-mosaic" if compiled else "interpret-smoke"

    # Evidence must never be replaced by strictly worse evidence: while a
    # prior COMPLETE record exists at out_path, only the final record of
    # a run that also completed may replace it; a prior partial one
    # yields once this run has harvested a case.  Print-only states still
    # reach stdout, which parents harvest.
    prior_exists = os.path.exists(out_path)
    prior_complete = False
    if prior_exists:
        try:
            with open(out_path) as f:
                prior_complete = json.load(f).get("progress") == "complete"
        except json.JSONDecodeError:
            pass

    phase_status: dict = {}
    cases: list = []

    def record_path(final_complete=False):
        if not prior_exists:
            return out_path
        if prior_complete:
            return out_path if final_complete else None
        return out_path if cases else None

    _write_record(probe, phase_status, cases, "started", record_path(),
                  mode)
    for i, phase in enumerate(PHASES):
        # per-phase budget: split what REMAINS over the remaining phases
        n_left = len(PHASES) - i
        budget = max(min(left() / n_left, left() - 10), 120.0)
        recs, status = _run_phase_subprocess(
            f"--phase={phase}", min(budget, left())
        )
        phase_status[phase] = status
        cases.extend(recs)
        _write_record(probe, phase_status, cases, f"{phase}-done",
                      record_path(), mode)

    # "complete" is reserved for a full sweep: every phase ok AND every
    # defined case ran (a killed phase must not read as completion)
    done = (all(st == "ok" for st in phase_status.values())
            and len(cases) == len(CASES))
    _write_record(probe, phase_status, cases,
                  "complete" if done else "incomplete",
                  record_path(final_complete=done), mode)
    bad = [c.get("case") for c in cases if not c.get("ok")]
    if not done or bad:
        print(f"verify_kernels_onchip: not accepted: phases={phase_status} "
              f"failed_cases={bad}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    if "--probe" in sys.argv:
        print(json.dumps(_probe()), flush=True)
    elif any(a.startswith("--phase=") for a in sys.argv):
        _phase_main(next(a.split("=", 1)[1] for a in sys.argv
                         if a.startswith("--phase=")))
    else:
        sys.exit(main())
