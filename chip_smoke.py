"""Quickest proof that the paper's pipeline still starts on the chip.

    python chip_smoke.py              # one TPU chip: materialize, serve, train
    python chip_smoke.py --four-chip  # four chips: sharded materialize + FSDP

One process, public entry points only (``tdx.deferred_init``,
``tdx.materialize_module``, ``ServeEngine``, ``Trainer``), weights random
from a seed.  Exits non-zero before doing any work when JAX finds no TPU,
and non-zero naming the phase when any phase fails.  Everything worth
reading (seconds, host RSS, compile counts, losses, tokens, device memory)
is printed on earlier lines; the LAST stdout line is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Default run (one chip, 16 GB):

- *materialize*: llama2_7b (6.74 B parameters, bf16) through
  ``deferred_init`` -> ``materialize_module`` onto the chip: parameters
  real and on the TPU; one compiled forward on (1, 256) tokens, finite;
  a sampled handful of parameters bit-identical to eager construction
  under the same ``tdx.manual_seed`` (the chip holds one 7B at a time, so
  the eager twin is built after the first is freed, and the model is then
  materialized again for the serve phase).
- *serve*: ``ServeEngine`` over those weights, slab cache then paged
  (``page_size=16``, prefix cache, ``decode_mode="persistent"``), a
  handful of mixed-length greedy requests, each engine beside the same
  engine over a ``use_flash=False`` model (the jnp path).  Random 7B
  logits have near-ties that bf16 rounding may flip, so token equality is
  reported, and what is REQUIRED is that every generated token of either
  engine is within ``SERVE_LOGIT_TOL`` of the maximum of a teacher-forced
  jnp forward's logits at its position.
- *serve_latent*: the DeepSeek-V3 family at kanana-2-30b-a3b's widths,
  cut to 2 layers (one dense, one with all 128 experts; 1.2 B
  parameters): the slab engine on the kernel path (``tdx_flash_forward``
  at qk 192 / v 128, ``tdx_latent_decode_attention``,
  ``tdx_grouped_matmul``) beside the jnp path, the same teacher-forced
  requirement at ``LATENT_LOGIT_TOL``, and the two paths' whole-prompt
  logits within ``LATENT_FORWARD_MEAN_TOL`` of each other in the mean.  ``--latent-only`` runs this
  phase alone.
- *serve_recurrent*: the Jamba family at AI21-Jamba2-3B's widths, one
  whole period (13 Mamba layers + 1 attention layer, 1.6 B parameters):
  the slab engine on the kernel path (``tdx_selective_scan`` in the
  bucketed prefills, ``tdx_selective_state_update`` over 12 decode
  steps, MQA 20 / 1 through ``tdx_flash_forward`` and
  ``tdx_decode_attention``) beside the jnp forms, the same
  teacher-forced requirement at ``RECURRENT_LOGIT_TOL`` and the two
  paths' whole-prompt logits within ``RECURRENT_FORWARD_MEAN_TOL`` in
  the mean.  ``--recurrent-only`` runs this phase alone.
- *train*: llama_1b at 2 x 2048 tokens, flash attention and
  AnyPrecisionAdamW, a few ``ShardedTrainStep`` steps through ``Trainer``:
  losses finite and falling, Pallas calls in the compiled step, zero
  compiles after warm-up.

``--four-chip`` runs only: llama2_7b ``materialize_module`` under
``fsdp_shard_rule`` over a 4-device mesh (every sharded parameter on four
distinct devices, per-device bytes about a quarter), then llama_1b FSDP
steps on that mesh against the same seed and batch on one device.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import resource
import sys
import time

#: a generated token's teacher-forced reference logit may sit this far
#: under the reference maximum (7B random-weight logits have std ~1.3;
#: the flash and jnp paths differ by bf16 rounding through 32 layers,
#: measured a few 1e-2; a wrong cache row or mask lands ~4 std away)
SERVE_LOGIT_TOL = 0.25
#: the DeepSeek-V3 family at real widths and 2 layers, kernel path
#: against jnp path (bf16; logits of std ~0.9).  The paths round the
#: attention's probabilities and the experts' sums at different points
#: (block by block within bf16 noise, PR 30's chip runs), and that noise
#: routes 4 tokens in a hundred otherwise at their sixth expert, which
#: moves such a token's logits by up to 0.8: so the MEAN difference of
#: one forward's logits is what is required (read 0.0098; a wrong row or
#: mask moves every logit by about one std), the largest is reported,
#: and a generated token may sit further under the teacher-forced
#: maximum than a dense model's (a wrong cache row lands ~4 std away)
LATENT_FORWARD_MEAN_TOL = 0.03
LATENT_LOGIT_TOL = 1.0
#: the Jamba family at real widths and one period, kernel path against
#: jnp forms (bf16; logits of std ~1).  Both paths keep the recurrent
#: state in float32 and do a step's arithmetic in the same order, so
#: they differ as the dense model's paths do; a state lost, a padding
#: row let into it or a slot's state written to another moves every
#: later logit by about one std
RECURRENT_FORWARD_MEAN_TOL = 0.03
RECURRENT_LOGIT_TOL = 0.25
#: sharded vs single-device loss, per step (bf16 params, f32 loss; the
#: two runs reduce in different orders)
FOUR_CHIP_LOSS_RTOL = 2e-2

SEED = 0
SAMPLED_PARAMS = (
    "tok_emb.weight",
    "blocks.0.attn.wq.weight",
    "blocks.15.mlp.w_down.weight",
    "blocks.31.attn_norm.weight",
    "lm_head.weight",
)


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def device_gb(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {
        k: round(stats[k] / 2**30, 3)
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        if k in stats
    }


def free_device_memory() -> None:
    """Drop what the caller no longer references: modules and engines
    hold reference cycles, so their arrays go only with a collection."""
    gc.collect()


class CompileLog:
    """Backend compiles (``RecompileWatcher``) and persistent-cache hits
    (``jax.monitoring`` events) since the last ``take()``."""

    def __init__(self):
        from jax import monitoring

        from torchdistx_tpu.obs import RecompileWatcher

        self.watcher = RecompileWatcher()
        self.hits = 0
        self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {
            "compiles": self.watcher.total,
            "compile_seconds": round(self.watcher.total_seconds, 2),
            "cache_hits": self.hits,
            "cache_misses": self.misses,
        }
        self.watcher.reset()
        self.hits = self.misses = 0
        return out


# -- phases -----------------------------------------------------------------


def _materialize(model_name: str, sharding_rule=None, ctor=None):
    """``deferred_init`` -> ``materialize_module`` under ``SEED``;
    ``ctor`` (zero arguments) where the model is not the Llama preset
    ``model_name``.  Returns (model, deferred seconds, materialize
    seconds)."""
    import jax

    import torchdistx_tpu as tdx
    from torchdistx_tpu.models import Llama

    t0 = time.time()
    tdx.manual_seed(SEED)
    model = tdx.deferred_init(ctor or (lambda: Llama.from_name(model_name)))
    t_deferred = time.time() - t0
    check(all(tdx.is_fake(p) for _, p in model.named_parameters()),
          "deferred_init produced a real parameter")
    t0 = time.time()
    tdx.materialize_module(model, sharding_rule=sharding_rule)
    jax.block_until_ready([p for _, p in model.named_parameters()])
    return model, t_deferred, time.time() - t0


def _sample(model) -> dict:
    import numpy as np

    named = dict(model.named_parameters())
    return {n: np.asarray(named[n]) for n in SAMPLED_PARAMS if n in named}


def _same_bits(a, b) -> bool:
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


def materialize_checked(log: CompileLog, model_name: str, sharding_rule=None,
                        ctor=None):
    """``deferred_init`` -> ``materialize_module``, with the checks every
    path wants: parameters real, ``jax.Array``s, on the accelerator.
    Returns the model."""
    import jax

    import torchdistx_tpu as tdx

    dev = jax.devices()[0]
    rss0 = rss_gb()
    model, t_deferred, t_mat = _materialize(model_name, sharding_rule, ctor)
    params = dict(model.named_parameters())
    nbytes = sum(p.nbytes for p in params.values())
    platforms = {d.platform for p in params.values() for d in p.devices()}
    check(not any(tdx.is_fake(p) for p in params.values()),
          "a parameter is still fake after materialize_module")
    check(all(isinstance(p, jax.Array) for p in params.values()),
          "a parameter is not a jax.Array")
    check(platforms == {dev.platform},
          f"parameters live on {platforms}, not on {dev.platform}")
    say("materialize", model=model_name, n_params=model.num_params(),
        param_gb=round(nbytes / 2**30, 3),
        deferred_init_s=round(t_deferred, 2),
        materialize_s=round(t_mat, 2),
        host_rss_peak_gb=round(rss_gb(), 3),
        host_rss_peak_before_gb=round(rss0, 3),
        device=device_gb(dev), **log.take())
    return model


def phase_materialize(log: CompileLog, model_name: str,
                      forward_tokens: int = 256):
    """Materialize, one forward, then the eager twin for a sampled
    handful of parameters.  Returns a materialized model.

    The chip holds one 7B at a time, and eager construction costs host
    memory that would hide the deferred path's own (``ru_maxrss`` is a
    high-water mark).  So: materialize first and read host RSS; keep a
    sampled handful on the host; free; construct the eager twin under the
    same seed and compare bit for bit; free; materialize again for the
    phases that follow (same seed, checked against the same handful)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import torchdistx_tpu as tdx
    from torchdistx_tpu.models import Llama
    from torchdistx_tpu.nn.module import functional_call

    dev = jax.devices()[0]
    model = materialize_checked(log, model_name)
    params = dict(model.named_parameters())

    if forward_tokens:
        tokens = jnp.asarray(
            np.random.RandomState(SEED).randint(
                0, model.cfg.vocab_size, (1, forward_tokens)
            ),
            jnp.int32,
        )
        t0 = time.time()
        compiled = jax.jit(
            lambda p, t: functional_call(model, p, (t,))
        ).lower(params, tokens).compile()
        t_compile = time.time() - t0
        n_kernels = compiled.as_text().count("tpu_custom_call")
        t0 = time.time()
        logits = np.asarray(compiled(params, tokens)).astype(np.float32)
        check(logits.shape == (1, forward_tokens, model.cfg.vocab_size),
              f"forward logits shape {logits.shape}")
        check(bool(np.isfinite(logits).all()), "forward logits not finite")
        check(n_kernels > 0, "forward holds no Pallas call (tpu_custom_call)")
        say("materialize", step="forward", tokens=forward_tokens,
            compile_s=round(t_compile, 2), run_s=round(time.time() - t0, 3),
            tpu_custom_calls=n_kernels,
            logits_std=round(float(logits.std()), 4), **log.take())
        del compiled, logits

    sampled = _sample(model)
    del model, params
    free_device_memory()
    t0 = time.time()
    tdx.manual_seed(SEED)
    eager = Llama.from_name(model_name)
    jax.block_until_ready([p for _, p in eager.named_parameters()])
    t_eager = time.time() - t0
    reference = _sample(eager)
    del eager
    free_device_memory()
    for name, want in reference.items():
        check(_same_bits(sampled[name], want),
              f"{name} differs from eager construction under the same seed")
    say("materialize", step="bit_identical_to_eager", params=sorted(sampled),
        eager_construct_s=round(t_eager, 2),
        host_rss_peak_after_eager_gb=round(rss_gb(), 3),
        device_after_free=device_gb(dev), **log.take())

    model, _, t_mat2 = _materialize(model_name)
    for name, want in _sample(model).items():
        check(_same_bits(sampled[name], want),
              f"{name} differs between two materializations of one seed")
    say("materialize", step="rematerialized", materialize_s=round(t_mat2, 2),
        device=device_gb(dev), **log.take())
    return model


def _serve_requests(vocab: int, lengths, max_new: int):
    import numpy as np

    rs = np.random.RandomState(SEED + 1)
    shared = rs.randint(1, vocab, 48).astype(np.int32)  # a common prefix
    reqs = []
    for i, n in enumerate(lengths):
        prompt = rs.randint(1, vocab, n).astype(np.int32)
        if i % 2 == 1 and n > shared.size:
            prompt[: shared.size] = shared
        reqs.append({"prompt": prompt, "max_new_tokens": max_new})
    return reqs


def phase_serve(log: CompileLog, model, *, max_len: int = 512,
                lengths=(12, 40, 64, 100, 200, 256), max_new: int = 12,
                buckets=(64, 256), modes=("slab", "paged"),
                logit_tol=SERVE_LOGIT_TOL, forward_mean_tol=None):
    """Slab and paged engines over ``model``'s weights, each against the
    jnp-path engine and a teacher-forced jnp forward.  With
    ``forward_mean_tol`` also one forward of the kernel-path model over
    the padded prompts against the jnp path's, the logits' mean
    difference within it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import torchdistx_tpu as tdx
    from torchdistx_tpu.nn.module import functional_call
    from torchdistx_tpu.serve import ServeEngine

    dev = jax.devices()[0]
    cfg = model.cfg
    params = dict(model.named_parameters())
    # the jnp twin shares the weights: a never-materialized module of the
    # same config with use_flash=False, driven through params=
    twin = tdx.deferred_init(
        lambda: type(model)(dataclasses.replace(cfg, use_flash=False))
    )
    reqs = _serve_requests(cfg.vocab_size, lengths, max_new)
    width = max(lengths) + max_new

    @jax.jit
    def reference_logits(p, toks):
        return functional_call(twin, p, (toks,)).astype(jnp.float32)

    def worst_gap(streams) -> float:
        """Teacher-forced check: the largest shortfall of a generated
        token's jnp-forward logit under that position's maximum."""
        toks = np.zeros((len(reqs), width), np.int32)
        for i, (r, out) in enumerate(zip(reqs, streams)):
            n = r["prompt"].size
            toks[i, :n] = r["prompt"]
            toks[i, n:n + out.size] = out
        logits = np.asarray(reference_logits(params, jnp.asarray(toks)))
        gap = 0.0
        for i, (r, out) in enumerate(zip(reqs, streams)):
            n = r["prompt"].size
            for j, tok in enumerate(out):
                row = logits[i, n + j - 1]
                gap = max(gap, float(row.max() - row[tok]))
        return gap

    if forward_mean_tol is not None:
        toks = np.zeros((len(reqs), max(lengths)), np.int32)
        for i, r in enumerate(reqs):
            toks[i, : r["prompt"].size] = r["prompt"]
        kernel = jax.jit(
            lambda p, t: functional_call(model, p, (t,)).astype(jnp.float32)
        )(params, jnp.asarray(toks))
        diff = jnp.abs(kernel - reference_logits(params, jnp.asarray(toks)))
        say("serve", step="forward_kernel_vs_jnp",
            max_logit_diff=round(float(diff.max()), 4),
            mean_logit_diff=round(float(diff.mean()), 5),
            logit_std=round(float(kernel.std()), 3),
            mean_tolerance=forward_mean_tol, **log.take())
        check(float(diff.mean()) <= forward_mean_tol,
              f"serve: kernel-path logits {float(diff.mean())} in the mean "
              f"from the jnp path's (tolerance {forward_mean_tol})")
        del kernel, diff
    extras = {
        "slab": {},
        "paged": dict(page_size=16, prefix_cache=True,
                      decode_mode="persistent"),
    }
    for mode, extra in ((m, extras[m]) for m in modes):
        streams = {}
        for path, m in (("kernel", model), ("jnp", twin)):
            t0 = time.time()
            engine = ServeEngine(
                m, params=params, num_slots=4, max_len=max_len,
                prefill_buckets=buckets, **extra,
            )
            results = engine.run([dict(r) for r in reqs])
            dt = time.time() - t0
            streams[path] = [np.asarray(r.tokens) for r in results]
            check(all(r.tokens.size == max_new and not r.truncated
                      for r in results),
                  f"serve/{mode}/{path}: a request ended early")
            counters = engine.metrics.counters
            say("serve", mode=mode, path=path, seconds=round(dt, 2),
                requests=len(reqs),
                tokens=int(sum(s.size for s in streams[path])),
                first_tokens=[int(s[0]) for s in streams[path]],
                kv_cache_gb=round(engine.cache.nbytes / 2**30, 3),
                programs=engine.num_compiled_programs(),
                host_syncs=int(counters.get("host_syncs", 0)),
                prefix_hit_tokens=int(counters.get("prefix_hit_tokens", 0)),
                card_peak_gb=round(engine.cost_book.max_peak_bytes() / 2**30,
                                   3),
                device=device_gb(dev), **log.take())
            if path == "kernel":
                _check_kernels_compiled(engine, mode)
            del engine, results
            free_device_memory()
        same = sum(int(np.array_equal(a, b)) for a, b in
                   zip(streams["kernel"], streams["jnp"]))
        agree = sum(int((a == b).sum()) for a, b in
                    zip(streams["kernel"], streams["jnp"]))
        gaps = {p: round(worst_gap(s), 4) for p, s in streams.items()}
        say("serve", mode=mode, step="kernel_vs_jnp",
            identical_streams=f"{same}/{len(reqs)}",
            identical_tokens=f"{agree}/{len(reqs) * max_new}",
            worst_logit_gap=gaps, tolerance=logit_tol, **log.take())
        for p, g in gaps.items():
            check(g <= logit_tol,
                  f"serve/{mode}/{p}: a generated token sits {g} under the "
                  f"teacher-forced jnp maximum (tolerance {logit_tol})")
    del twin


def phase_serve_latent(log: CompileLog):
    """The DeepSeek-V3 family at real widths through the same serve
    phase: latent cache, absorbed decode kernel, grouped experts."""
    from torchdistx_tpu.models import DeepseekV3

    model = materialize_checked(
        log, "kanana_2_30b_a3b/2-layers",
        ctor=lambda: DeepseekV3.from_name(
            "kanana_2_30b_a3b", n_layers=2, max_seq_len=1024
        ),
    )
    phase_serve(log, model, modes=("slab",), logit_tol=LATENT_LOGIT_TOL,
                forward_mean_tol=LATENT_FORWARD_MEAN_TOL)


def phase_serve_recurrent(log: CompileLog):
    """The Jamba family at real widths through the same serve phase:
    recurrent state beside KV rows in the slab, the selective-scan
    prefill kernel told each prompt's true length, the in-place
    state-update decode kernel."""
    from torchdistx_tpu.models import Jamba

    model = materialize_checked(
        log, "jamba2_3b/1-period",
        ctor=lambda: Jamba.from_name(
            "jamba2_3b", n_layers=14, max_seq_len=1024
        ),
    )
    phase_serve(log, model, modes=("slab",), logit_tol=RECURRENT_LOGIT_TOL,
                forward_mean_tol=RECURRENT_FORWARD_MEAN_TOL)


def _check_kernels_compiled(engine, mode: str) -> None:
    """Every program the kernel-path engine dispatched holds Mosaic
    custom calls, by its cost card: proof the Pallas path was the
    compiled one, not interpret mode and not the jnp path."""
    found = {
        name: card.pallas_calls
        for name, card in sorted(engine.cost_book.cards().items())
    }
    say("serve", mode=mode, step="pallas_calls_per_program", programs=found)
    # a WARM prefill (prefix hit or later chunk) attends at a traced cache
    # offset, for which the repo has no kernel: it is the jnp band by
    # design (ops/attention.cached_attention), on every platform
    must = {n: c for n, c in found.items() if "/prefill/warm/" not in n}
    check(bool(must) and all(must.values()),
          f"serve/{mode}: a serve program holds no tpu_custom_call: {found}")


def run_train(log: CompileLog, mesh, *, model_name: str = "llama_1b",
              batch: int = 2, seq: int = 2048, steps: int = 4,
              label: str = "train"):
    """``model_name`` with flash attention and AnyPrecisionAdamW, as
    ``utils.benchmarks.build_train_workload`` builds them, behind a
    ``ShardedTrainStep`` on ``mesh``: a few steps through ``Trainer`` on
    ONE repeated batch (the loss must fall on it).  Returns the per-step
    losses."""
    import jax
    import numpy as np

    import torchdistx_tpu as tdx
    from torchdistx_tpu.models import Llama
    from torchdistx_tpu.nn import functional
    from torchdistx_tpu.nn.module import functional_call
    from torchdistx_tpu.optimizers import anyprecision_adamw
    from torchdistx_tpu.parallel import ShardedTrainStep, fsdp_shard_rule
    from torchdistx_tpu.trainer import Trainer

    tdx.manual_seed(SEED)
    model = tdx.deferred_init(Llama.from_name, model_name, max_seq_len=seq)
    tdx.materialize_module(model, sharding_rule=fsdp_shard_rule(mesh))

    def loss_fn(p, b):
        tokens, labels = b
        return functional.cross_entropy(
            functional_call(model, p, (tokens,)), labels
        )

    step = ShardedTrainStep(
        loss_fn, anyprecision_adamw(1e-4), mesh, shard_axis="fsdp"
    )
    rs = np.random.RandomState(SEED)
    batch_np = tuple(
        rs.randint(0, model.cfg.vocab_size, (batch, seq)).astype(np.int32)
        for _ in range(2)
    )
    params = dict(model.named_parameters())
    losses = []
    trainer = Trainer(
        step, params, tokens_per_batch=batch * seq, log_every=1,
        log_fn=lambda m: losses.append(m) if "loss" in m else None,
    )
    t0 = time.time()
    trainer.fit([batch_np], num_steps=1)  # warm-up: compile + cost card
    jax.block_until_ready(trainer.params)
    warm = log.take()
    say(label, step="warmup", seconds=round(time.time() - t0, 2),
        n_params=model.num_params(), devices=mesh.devices.size, **warm)
    t0 = time.time()
    trainer.fit([batch_np] * steps, num_steps=1 + steps)
    jax.block_until_ready(trainer.params)
    dt = time.time() - t0
    steady = log.take()
    series = [round(float(m["loss"]), 5) for m in losses]
    card = trainer.cost_card
    check(card is not None, f"{label}: the step's cost card was not captured")
    kernels = card.pallas_calls
    say(label, step="steady", steps=steps, seconds=round(dt, 2),
        losses=series, tpu_custom_calls=kernels, cost_card_flops=card.flops,
        device=[device_gb(d) for d in mesh.devices.flat], **steady)
    check(len(series) >= steps, f"{label}: {len(series)} losses logged")
    check(bool(np.isfinite(series).all()), f"{label}: loss not finite")
    check(series[-1] < series[0], f"{label}: loss did not fall: {series}")
    check(kernels > 0, f"{label}: compiled step holds no tpu_custom_call")
    check(steady["compiles"] == 0,
          f"{label}: {steady['compiles']} compiles after warm-up")
    del trainer, model, step, params
    free_device_memory()
    return series


def phase_train(log: CompileLog):
    import numpy as np
    from jax.sharding import Mesh

    import jax

    mesh = Mesh(np.array(jax.devices()[:1]), ("fsdp",))
    run_train(log, mesh)


def phase_four_chip(log: CompileLog):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from torchdistx_tpu.parallel import create_mesh, fsdp_shard_rule

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chip needs 4 devices, found "
          f"{len(devices)}")
    mesh = create_mesh({"fsdp": 4})
    before = [device_gb(d) for d in devices]
    model = materialize_checked(log, "llama2_7b", fsdp_shard_rule(mesh))
    params = dict(model.named_parameters())
    total = sum(p.nbytes for p in params.values())
    per_device = {d.id: 0 for d in devices}
    n_sharded = 0
    for name, p in params.items():
        shards = p.addressable_shards
        if not p.sharding.is_fully_replicated:
            n_sharded += 1
            check(len({s.device.id for s in shards}) == 4,
                  f"{name}: shards on "
                  f"{sorted({s.device.id for s in shards})}, not 4 devices")
        for s in shards:
            per_device[s.device.id] += s.data.nbytes
    stats = [device_gb(d) for d in devices]
    say("four_chip", step="sharded_materialize", sharded_params=n_sharded,
        replicated_params=len(params) - n_sharded,
        total_gb=round(total / 2**30, 3),
        per_device_gb={k: round(v / 2**30, 3) for k, v in per_device.items()},
        memory_stats_before=before, memory_stats=stats)
    for dev_id, nbytes in per_device.items():
        check(abs(nbytes / total - 0.25) < 0.01,
              f"device {dev_id} holds {nbytes / total:.3f} of the bytes")
    in_use = [s.get("bytes_in_use") for s in stats]
    if all(v is not None for v in in_use):
        check(max(in_use) - min(in_use) < 0.05 * max(in_use) + 0.05,
              f"device memory is uneven after materialize: {in_use}")
        check(all(s["peak_bytes_in_use"] < 0.5 * total / 2**30
                  for s in stats),
              f"a device peaked above half the model: {stats}")
    del model, params
    free_device_memory()

    sharded = run_train(log, mesh, batch=4, label="four_chip/fsdp4")
    single = run_train(
        log, Mesh(np.array(devices[:1]), ("fsdp",)), batch=4,
        label="four_chip/single",
    )
    rel = [abs(a - b) / abs(b) for a, b in zip(sharded, single)]
    say("four_chip", step="sharded_vs_single", sharded=sharded,
        single=single, rel_diff=[round(r, 5) for r in rel],
        tolerance=FOUR_CHIP_LOSS_RTOL)
    check(max(rel) <= FOUR_CHIP_LOSS_RTOL,
          f"sharded and single-device losses differ by {max(rel)}")


# -- entry ------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip sharded path")
    ap.add_argument("--latent-only", action="store_true",
                    help="run only the latent-cache serve phase")
    ap.add_argument("--recurrent-only", action="store_true",
                    help="run only the recurrent-state serve phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform == "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2

    from torchdistx_tpu.utils.compile_cache import use_compile_cache

    t_start = time.time()
    say("start", jax=jax.__version__, devices=len(devices),
        kind=devices[0].device_kind, compile_cache=use_compile_cache(),
        four_chip=args.four_chip)
    log = CompileLog()
    phase = "start"
    try:
        if args.four_chip:
            phase = "four_chip"
            phase_four_chip(log)
        elif args.latent_only:
            phase = "serve_latent"
            phase_serve_latent(log)
        elif args.recurrent_only:
            phase = "serve_recurrent"
            phase_serve_recurrent(log)
        else:
            phase = "materialize"
            model = phase_materialize(log, "llama2_7b")
            phase = "serve"
            phase_serve(log, model)
            del model
            free_device_memory()
            phase = "serve_latent"
            phase_serve_latent(log)
            free_device_memory()
            phase = "serve_recurrent"
            phase_serve_recurrent(log)
            free_device_memory()
            phase = "train"
            phase_train(log)
    except Exception as e:  # the boundary: name the phase, exit non-zero
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: phase {phase!r} FAILED: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        say("failed", failed_phase=phase, error=f"{type(e).__name__}: {e}"[
            :500])
        return 1
    say("done", seconds=round(time.time() - t_start, 1),
        host_rss_gb=round(rss_gb(), 3))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
