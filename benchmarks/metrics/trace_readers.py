"""Readers of the per-layer metrics that come from the device trace.

A kernel is found by the name its Pallas call carries (``name=`` on every
``pl.pallas_call`` of the program since PR 27; the trace shows it as the
operation's name, numbered: ``tdx_flash_forward.3``), matched exactly:
see ``KERNELS``.  A Mosaic kernel of another name -- fused
cross-entropy's, a later latent-decode or grouped-expert kernel -- enters
none of these metrics; it brings a reader and a metric of its own.  A
reader that finds no such operation returns None, never 0.  What a call
needs is counted from the shapes by ``harness.counts``: the causal half
for flash attention, the visible rows for decode."""

from __future__ import annotations

from harness import counts, peaks, tracered

#: metric's kernel -> the program's names of the Pallas calls it is made of
KERNELS = {
    "flash_fwd": ("tdx_flash_forward",),
    # the backward pass is two kernels: dK/dV, then dQ
    "flash_bwd": ("tdx_flash_backward_dkv", "tdx_flash_backward_dq"),
    # slab and paged caches have a kernel each; a program holds one of them
    "decode_attn": ("tdx_decode_attention", "tdx_paged_decode_attention"),
}


def is_kernel(kernel: str):
    """``match(name, tag)`` for ``tracered.kernel_seconds``: a Mosaic
    kernel (tag ``pallas``) whose name, less the number the compiler
    appends, is one of ``KERNELS[kernel]``."""
    names = KERNELS[kernel]
    return lambda name, tag: (tag.startswith("pallas")
                              and tracered.base_name(name) in names)


def _seconds(ctx, kernel):
    if ctx.reduction is None:
        return 0.0, 0
    return tracered.kernel_seconds(ctx.reduction["ops"], is_kernel(kernel))


def device_idle_pct(ctx):
    return None if ctx.reduction is None else ctx.reduction["idle_pct"]


def train_flash_roofline(ctx):
    """Forward and backward calls together: what the calls seen need at
    the roofline over the device time they took.  A recomputed forward
    (remat) is a call like any other here; its cost shows in the step's
    MFU, which counts no recomputation."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t_f, n_f = _seconds(ctx, "flash_fwd")
    t_b, n_b = _seconds(ctx, "flash_bwd")
    if not n_f or not n_b:
        return None
    b = ctx.counters["train.batch"] // ctx.chips
    s = ctx.counters["train.seq"]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    need_f, _ = counts.roofline_seconds(
        counts.flash_causal_flops(b, s, h, d), counts.flash_bytes(b, s, h, kv, d), peak)
    need_b, _ = counts.roofline_seconds(
        counts.flash_causal_flops(b, s, h, d, backward=True),
        counts.flash_bytes(b, s, h, kv, d, backward=True), peak)
    # the backward pass is two kernels (dK/dV and dQ) to one need
    n_b_calls = n_b / 2.0
    return 100.0 * (n_f * need_f + n_b_calls * need_b) / (t_f + t_b)


def serve_decode_attn_roofline(ctx):
    """Bytes of the cache rows visible to the decode steps traced, over
    the bandwidth, against the kernel's device time.  The rows come from
    the benchmark's own count of the window, scaled to the steps that
    the trace holds (one kernel call a layer and step)."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t, n = _seconds(ctx, "decode_attn")
    steps = ctx.counters.get("serve.decode_dispatches")
    if not n or not steps:
        return None
    layers = cfg["num_hidden_layers"]
    rows_per_step = ctx.counters["serve.decode_rows_sum"] / steps
    traced_steps = n / layers
    nbytes = counts.decode_attention_bytes(
        rows_per_step * traced_steps, cfg["num_key_value_heads"],
        cfg["head_dim"]) * layers
    flops = counts.decode_attention_flops(
        rows_per_step * traced_steps, cfg["num_attention_heads"],
        cfg["head_dim"]) * layers
    need, _ = counts.roofline_seconds(flops, nbytes, peak)
    return 100.0 * need / t


def serve_flash_prefill_roofline(ctx):
    """Causal FLOPs of the true prompt lengths (not the padded bucket)
    against the kernel's time, over the prefills that the trace holds:
    the window's mean prompt stands for each traced call."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t, n = _seconds(ctx, "flash_fwd")
    lens = ctx.counters.get("serve.prompt_lens")
    if not n or not lens:
        return None
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    per_prompt = [counts.roofline_seconds(
        counts.flash_causal_flops(1, p, h, d),
        counts.flash_bytes(1, p, h, kv, d), peak)[0] for p in lens]
    mean_need = sum(per_prompt) / len(per_prompt)
    return 100.0 * n * mean_need / t
