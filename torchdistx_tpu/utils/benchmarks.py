"""Shared single-chip training workload for bench.py and the profiler.

``bench.py --train-phase`` measures this workload's throughput and
``scripts/profile_train_step.py`` traces the SAME workload — sharing the
builder keeps "what we profile" identical to "what we score".

Env overrides (smoke tests / experiments): ``TDX_BENCH_TRAIN_MODEL``,
``TDX_BENCH_BATCH``, ``TDX_BENCH_SEQ``, ``TDX_BENCH_REMAT``,
``TDX_BENCH_OPT`` ("anyprecision" default; "8bit" =
``optimizers.adamw_8bit`` — the optimizer-HBM-traffic A/B).
"""

from __future__ import annotations

import functools
import os
from typing import Any

#: Peak dense bf16 FLOP/s of one chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" system architecture
#: (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip); jax reports the
#: v5e as "TPU v5 lite" (chip_smoke.py on the chip, PR 24).  A kind that
#: is not here has no utilization: :func:`peak_bf16_flops` raises, it
#: never falls back to some other chip's peak.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_bf16_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s for ``device_kind``; ``LookupError`` for a kind
    with no peak on record (a CPU, an unlisted accelerator)."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise LookupError(
            f"no bf16 peak on record for device kind {device_kind!r} "
            f"(known: {sorted(PEAK_BF16_FLOPS)}); utilization is not "
            "defined there"
        ) from None


def warm_to_steady_state(
    run,
    carry,
    sync,
    max_calls: int = 5,
    watcher=None,
    label: str = "warm_to_steady_state",
):
    """Call ``run(carry) -> (carry, aux)`` until no call compiles anything
    new, returning ``(carry, warm_times, converged)``.  ``converged`` is
    False when ``max_calls`` ran out with the compile cache still growing
    (or the timing fallback never stabilizing) — callers MUST surface it:
    a timed window after a non-converged warm-up may still contain a
    recompile, the exact measurement bug this helper exists to prevent.

    One warm call is NOT enough for a donated-carry jit: the first call
    compiles, and the second triggers a full recompile because the donated
    carry comes back with executable-chosen layouts that differ from the
    host-staged originals — a new input-layout signature.  (Round-2's
    "5.5% MFU" was a timed window that caught that hidden 30 s+ recompile;
    steady state measures ~9x faster.)  ``sync(aux)`` must block until the
    call's work is done (e.g. fetch a loss to host).

    Steadiness signals, best first: an ``obs.RecompileWatcher`` passed as
    ``watcher`` counts actual backend compiles per call (each call runs
    under ``recompile_scope(label)``, so the donated-carry recompile lands
    in ``watcher.counts[label]`` as an ASSERTABLE number — exactly 1 extra
    compile on donation-capable backends, 0 on the CPU mesh where donation
    is a no-op); then the jit cache size reaching a fixpoint
    (``utils.compat.jit_cache_size``); then a timing heuristic where the
    private API is unavailable and no watcher was given.
    """
    import contextlib
    import time

    from .compat import jit_cache_size

    warm_times = []
    prev_cache = -1
    converged = False
    for _ in range(max_calls):
        before = watcher.total if watcher is not None else None
        scope = (
            watcher.scope(label)
            if watcher is not None
            else contextlib.nullcontext()
        )
        t0 = time.perf_counter()
        with scope:
            carry, aux = run(carry)
            sync(aux)
        warm_times.append(time.perf_counter() - t0)
        cur_cache = jit_cache_size(run)
        if watcher is not None and watcher.available:
            if watcher.total == before:
                converged = True  # this call compiled nothing -> steady
                break
        elif cur_cache is not None:
            if cur_cache == prev_cache:
                converged = True  # no compile happened this call -> steady
                break
            prev_cache = cur_cache
        elif (
            len(warm_times) >= 2
            and warm_times[-1] == min(warm_times)
            and abs(warm_times[-1] - warm_times[-2]) < 0.3 * warm_times[-1]
        ):
            converged = True
            break
    return carry, warm_times, converged


def build_train_workload(n_steps: int) -> dict[str, Any]:
    """Build the benchmark training workload: a 1B-class Llama LM step
    (flash attention on TPU, AnyPrecisionAdamW, bf16; remat off by
    default — see the ``remat`` note below).

    Returns ``{"run", "carry", "name", "n_params", "batch", "seq",
    "flops_per_token", "remat"}`` where ``run(carry) -> (carry, losses)``
    executes ``n_steps`` device-side (lax.scan) with donated buffers.
    Under ``TDX_BENCH_ZERO2=1`` (multi-device only) the dict gains the
    plan/byte fields the A/B verdict pins (``plan``, ``zero2_dp``,
    ``optimizer_bytes[_per_device]``, ``zero2_*_bytes``).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import torchdistx_tpu as tdx
    from torchdistx_tpu.models import Llama, llama_configs
    from torchdistx_tpu.nn import functional
    from torchdistx_tpu.nn.module import functional_call
    from torchdistx_tpu.optimizers import anyprecision_adamw

    name = os.environ.get("TDX_BENCH_TRAIN_MODEL", "llama_1b")
    batch = int(os.environ.get("TDX_BENCH_BATCH", "2"))
    seq = int(os.environ.get("TDX_BENCH_SEQ", "2048"))
    # remat off by default at the bench shape: batch 2 x 2048 activations
    # fit v5e HBM un-rematted and measure 19.2k tok/s / 0.64 MFU vs
    # 15.6k / 0.52 rematted (the recompute is ~23% of step time).  Set
    # TDX_BENCH_REMAT=1 for configs whose activations don't fit (batch>=4).
    remat = os.environ.get("TDX_BENCH_REMAT", "0") == "1"
    # TDX_BENCH_REMAT_POLICY=dots: save matmul outputs, recompute only
    # elementwise work — the A/B against full-block recompute (~23% of
    # the step, BASELINE.md) for shapes that need remat at all
    remat_policy = os.environ.get("TDX_BENCH_REMAT_POLICY", "full")
    if remat_policy != "full" and not remat:
        raise ValueError(
            "TDX_BENCH_REMAT_POLICY has no effect without TDX_BENCH_REMAT=1"
            " — refusing to run an A/B leg that silently never remats"
        )

    tdx.manual_seed(0)
    model = tdx.deferred_init(
        Llama.from_name, name, max_seq_len=seq, remat=remat,
        remat_policy=remat_policy,
    )
    tdx.materialize_module(model)
    params = dict(model.named_parameters())
    n_params = model.num_params()

    # TDX_BENCH_ZERO2=1: partition the *update* — params stay replicated
    # over a dp mesh spanning every visible device while the declarative
    # plan (parallel/plan.py) shards optimizer state 1/dp and prices the
    # step's params all-gather closed-form.  The A/B verdict vs the
    # replicated baseline: optimizer bytes/device strictly drop; step
    # wire bytes pin exactly to (n-1)/n * param_bytes.
    zero2 = os.environ.get("TDX_BENCH_ZERO2", "0") == "1"
    plan = None
    if zero2:
        n_dev = jax.device_count()
        if n_dev < 2:
            raise ValueError(
                "TDX_BENCH_ZERO2=1 needs a multi-device mesh "
                f"(have {n_dev} device(s)); the bench driver skips this "
                "arm honestly on single-chip platforms"
            )
        from ..parallel import ShardingPlan
        from ..parallel.mesh import create_mesh

        mesh = create_mesh({"dp": n_dev})
        plan = ShardingPlan(mesh, dp_axis="dp", zero2=True,
                            min_shard_elems=1)
        params = plan.apply(params)

    # TDX_BENCH_OPT=8bit swaps in the blockwise-quantized moments
    # (optimizers.adamw_8bit) — the optimizer-HBM-traffic A/B: ~3x fewer
    # optimizer bytes/step against AnyPrecision's f32 m + bf16 v.
    opt_name = os.environ.get("TDX_BENCH_OPT", "anyprecision")
    if opt_name == "8bit":
        from ..optimizers import adamw_8bit

        tx = adamw_8bit(1e-4)
        opt_label = "adamw_8bit"
    else:
        tx = anyprecision_adamw(1e-4)
        opt_label = "anyprecision_adamw"
    opt_state = tx.init(params)
    if plan is not None:
        # plan-derived placement: param-shaped slots shard 1/dp, scalar
        # counts stay replicated (derive_optimizer_state_shardings)
        opt_state = jax.device_put(
            opt_state, plan.optimizer_state_shardings(opt_state, params)
        )

    cfg = llama_configs[name]
    vocab = cfg.get("vocab_size", 32000)
    rs = np.random.RandomState(0)
    tokens = jnp.asarray(rs.randint(0, vocab, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rs.randint(0, vocab, (batch, seq)), jnp.int32)

    # TDX_BENCH_FUSED_CE=1: route the loss through the fused LM-head CE
    # kernels (ops/fused_ce.py) — no (B, S, vocab) logits in HBM; the
    # vocab-fusion A/B from the round-3 profile's ~15 ms/step finding.
    fused_ce = os.environ.get("TDX_BENCH_FUSED_CE", "0") == "1"
    if fused_ce:
        from ..ops.fused_ce import fused_linear_cross_entropy

        def loss_fn(p):
            h = functional_call(
                model, p, (tokens,), {"return_hidden": True}
            )
            return fused_linear_cross_entropy(
                h, p["lm_head.weight"], labels
            )

    else:

        def loss_fn(p):
            return functional.cross_entropy(
                functional_call(model, p, (tokens,)), labels
            )

    # numerics observatory (obs/numerics.py): under TDX_NUMERICS=1 the
    # scanned step also emits per-group digests (params / loss / grads),
    # reduced across steps INSIDE the same jitted program — the bench
    # record embeds them with zero extra dispatches, same discipline as
    # the serve engine.  aux becomes (losses, digests).
    from ..obs.numerics import numerics_enabled

    num_on = numerics_enabled()

    def step(carry, _):
        p, s = carry
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, s = tx.update(grads, s, p)
        p = jax.tree_util.tree_map(lambda a, u: a + u, p, updates)
        if num_on:
            from ..obs.numerics import array_digest, tree_group_digest

            digs = tree_group_digest(p, "params/")
            digs["loss"] = array_digest(loss)
            digs.update(tree_group_digest(grads, "grads/"))
            return (p, s), (loss, digs)
        return (p, s), loss

    # N steps in ONE jitted lax.scan, so the timed window holds no host
    # dispatch; donation reuses the params/optimizer buffers (the chip
    # is nearly full).  The donated carry
    # keeps its arrival placements via out_shardings (TDX101) — layout
    # (tiling) choices remain jit's, so warm_to_steady_state is still
    # required before timing.
    from ..parallel.fsdp import donated_carry_shardings

    if plan is not None:
        # the plan cites the carry layouts (TDX101): the placement the
        # donated scan pins is the one the plan priced
        (carry_sh,) = plan.shardings_for((params, opt_state))
    else:
        (carry_sh,) = donated_carry_shardings((params, opt_state))

    @functools.partial(
        jax.jit, donate_argnums=(0,), out_shardings=(carry_sh, None)
    )
    def run(carry):
        if num_on:
            from ..obs.numerics import reduce_stacked_digests

            carry, (losses, stacked) = lax.scan(
                step, carry, None, length=n_steps
            )
            return carry, (losses, reduce_stacked_digests(stacked))
        return lax.scan(step, carry, None, length=n_steps)

    # model FLOPs per token: 6N for fwd+bwd matmuls + attention term
    # 12 * L * dim * seq (PaLM appendix convention)
    flops_per_token = 6 * n_params + 12 * cfg["n_layers"] * cfg["dim"] * seq
    out = {
        "run": run,
        "carry": (params, opt_state),
        "name": name,
        "n_params": int(n_params),
        "batch": batch,
        "seq": seq,
        "flops_per_token": flops_per_token,
        "remat": remat,
        "remat_policy": remat_policy,
        "optimizer": opt_label,
        "fused_ce": fused_ce,
        "zero2": zero2,
        "numerics": num_on,
    }
    if plan is not None:

        def _tree_bytes(tree):
            return int(
                sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))
            )

        def _tree_bytes_per_device(tree):
            # exact per-device footprint from the ACTUAL placements (not
            # the plan's intent): shard_shape accounts for leaves too
            # small or indivisible to shard
            total = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                n = 1
                for d in leaf.sharding.shard_shape(leaf.shape):
                    n *= d
                total += n * leaf.dtype.itemsize
            return int(total)

        dp = int(plan.mesh.shape["dp"])
        out.update(
            plan=f"zero2(dp={dp})",
            zero2_dp=dp,
            optimizer_bytes=_tree_bytes(opt_state),
            optimizer_bytes_per_device=_tree_bytes_per_device(opt_state),
            zero2_participating_bytes=int(
                plan.zero2_participating_bytes(params)
            ),
            zero2_step_wire_bytes=int(plan.step_wire_bytes(params)),
        )
    return out
