"""Readers of the DeepSeek-V3 family's per-layer metrics: its own
kernels (``tdx_latent_decode_attention``, ``tdx_grouped_matmul``, and
``tdx_flash_forward`` at qk width != v width) and its expert counters.

What a call needs comes from the family's counts
(``families/deepseek_v3_counts.py``: operations and bytes from the
shapes), the device time from the trace, the rows and groups from the
program's own counters: the serve programs sum them on the device and
``ServeMetrics`` fetches them when it is read, which the engine's driver
has no reason to do -- so the reader does, through
``serve.metrics.latest_metrics()`` (the metrics object outlives the
engine; it holds numbers, never a cache or a weight).  A reader that
finds nothing to read -- a program without that kernel or without those
counters, as the parent of the PR that brought them -- returns None,
never 0."""

from __future__ import annotations

from harness import counts, peaks, tracered

LATENT = "tdx_latent_decode_attention"
GROUPED = "tdx_grouped_matmul"
FLASH = "tdx_flash_forward"


def _seconds(ctx, kernel: str):
    if ctx.reduction is None:
        return 0.0, 0
    return tracered.kernel_seconds(
        ctx.reduction["ops"],
        lambda name, tag: (tag.startswith("pallas")
                           and tracered.base_name(name) == kernel))


def _need(ctx, name: str):
    return getattr(ctx.family("counts." + name).counts, name)


def _moe_counters(ctx):
    """The window's expert counters, or None where the program has none."""
    try:
        from torchdistx_tpu.serve.metrics import latest_metrics
    except ImportError:
        return None
    m = latest_metrics()
    if m is None or not hasattr(m, "sync_device_counters"):
        return None
    m.sync_device_counters()
    got = {k: v for k, v in m.counters.items() if k.startswith("moe_")}
    return got or None


def _expert_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - min(
        cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def serve_latent_decode_roofline(ctx):
    """What the visible latent rows of the traced decode steps need at
    the roofline (every row read once; the absorbed form's operations),
    over the kernel's device time.  The rows come from the benchmark's
    own count of the window, scaled to the steps the trace holds (one
    kernel call a layer and step), as ``serve.decode_attn_roofline``
    scales."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t, n = _seconds(ctx, LATENT)
    steps = ctx.counters.get("serve.decode_dispatches")
    if not n or not steps:
        return None
    layers = cfg["num_hidden_layers"]
    rows_per_step = ctx.counters["serve.decode_rows_sum"] / steps
    flops, nbytes = _need(ctx, "latent_decode_need")(
        cfg, rows_per_step * (n / layers))
    need, _ = counts.roofline_seconds(flops * layers, nbytes * layers, peak)
    return 100.0 * need / t


def serve_grouped_matmul_roofline(ctx):
    """The larger of the window's expert FLOPs over the peak and its
    bytes over the bandwidth (weights of the experts touched, rows in
    and out), scaled to the kernel calls the trace holds, over their
    device time.  The need is taken on the window's totals, which can
    only under-read (the larger of two sums is no more than the sum of
    the larger)."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t, n = _seconds(ctx, GROUPED)
    moe = _moe_counters(ctx)
    if not n or not moe or not moe.get("moe_routed_rows"):
        return None
    dispatches = (ctx.counters.get("serve.decode_dispatches", 0)
                  + ctx.counters.get("serve.prefill_calls", 0))
    # an expert layer calls the kernel twice: gate and up fused, then down
    calls = 2 * _expert_layers(cfg) * dispatches
    if not calls:
        return None
    flops, nbytes = _need(ctx, "grouped_matmul_need")(
        cfg, moe["moe_routed_rows"], moe["moe_groups"])
    need, _ = counts.roofline_seconds(flops, nbytes, peak)
    return 100.0 * need * (n / calls) / t


def serve_mla_prefill_roofline(ctx):
    """Causal attention in the expanded widths over the true prompt
    lengths (not the padded bucket) against ``tdx_flash_forward``'s
    time: the window's mean prompt stands for each traced call."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t, n = _seconds(ctx, FLASH)
    lens = ctx.counters.get("serve.prompt_lens")
    if not n or not lens:
        return None
    need_of = _need(ctx, "mla_prefill_need")
    per_prompt = [counts.roofline_seconds(*need_of(cfg, p), peak)[0]
                  for p in lens]
    return 100.0 * n * (sum(per_prompt) / len(per_prompt)) / t


def serve_moe_rows_per_group(ctx):
    """(Token, expert) rows a touched expert of the window's decode
    steps: about 2 where every step streams most experts' weights for a
    row or two each, in the tens where the matmuls have rows to work
    on."""
    moe = _moe_counters(ctx)
    if not moe or not moe.get("moe_groups_decode"):
        return None
    return moe["moe_routed_rows_decode"] / moe["moe_groups_decode"]
