"""Readers of the Qwen3-Next family's per-layer metrics: its own kernels
(``tdx_gated_delta_update``, ``tdx_gated_delta_chunk``), the grouped
expert matmul over the SHARE of the experts the configuration holds, the
decode attention kernel at the family's attention layers (a few of many:
the generic ``serve.decode_attn_roofline`` multiplies by
``num_hidden_layers``), and the share of the router's choices that fall
on the experts held.

What a call needs comes from the family's counts
(``families/qwen3_next_counts.py``: operations and bytes from the shapes
and the configuration's stated dtypes), the device time from the trace,
the tokens from the benchmark's own count of the window and the rows
and groups from the program's counters (``serve.metrics.latest_metrics()``:
the metrics object outlives the engine; it holds numbers, never a cache
or a weight).  A reader that finds nothing to read -- a program without
that kernel or those counters -- returns None, never 0.  No share can
read above 100: the needs count the true tokens, the slots that decoded
and the rows and experts the counters saw; the kernels work the
bucket's rows and every slot."""

from __future__ import annotations

from harness import counts, peaks, tracered

UPDATE = "tdx_gated_delta_update"
CHUNK = "tdx_gated_delta_chunk"
GROUPED = "tdx_grouped_matmul"
DECODE_ATTN = "tdx_decode_attention"


def _seconds(ctx, kernel: str):
    """Device time and count of the operations that carry the kernel's
    name: the Mosaic call itself (tag ``pallas``), or the ``fusion`` the
    compiler wraps around it under the call's own name (a prefill's
    kernel fused with the write of its final state into the slab)."""
    if ctx.reduction is None:
        return 0.0, 0
    return tracered.kernel_seconds(
        ctx.reduction["ops"],
        lambda name, tag: (tag.startswith(("pallas", "fusion"))
                           and tracered.base_name(name) == kernel))


def _counts(ctx, *names):
    return ctx.family(*("counts." + n for n in names)).counts


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


def serve_gdn_update_roofline(ctx):
    """The state of the slots that decoded a token, read once and
    written once, with their row operands, over the bandwidth (or the
    delta rule's operations over the peak, whichever is larger), against
    the kernel's device time: one call a Gated-DeltaNet layer and decode
    step.  The slots that decoded come from the benchmark's own count of
    the window (tokens delivered less the prompts' first tokens, a
    step)."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t, n = _seconds(ctx, UPDATE)
    steps = ctx.counters.get("serve.decode_dispatches")
    if not n or not steps:
        return None
    decoded = ctx.counters["serve.tokens"] - len(ctx.counters["serve.prompt_lens"])
    c = _counts(ctx, "gdn_update_need")
    need, _ = counts.roofline_seconds(
        *c.gdn_update_need(cfg, decoded / steps), peak)
    return 100.0 * n * need / t


def serve_gdn_chunk_roofline(ctx):
    """The chunked delta rule over the TRUE prompt lengths (not the
    padded bucket) against ``tdx_gated_delta_chunk``'s time: the window's
    mean prompt stands for each traced call (one a Gated-DeltaNet layer
    and prefill)."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t, n = _seconds(ctx, CHUNK)
    lens = ctx.counters.get("serve.prompt_lens")
    if not n or not lens:
        return None
    c = _counts(ctx, "gdn_chunk_need")
    per_prompt = [counts.roofline_seconds(*c.gdn_chunk_need(cfg, p), peak)[0]
                  for p in lens]
    return 100.0 * n * (sum(per_prompt) / len(per_prompt)) / t


def serve_expert_share_matmul_roofline(ctx):
    """The larger of the window's HELD expert FLOPs over the peak and
    their bytes over the bandwidth (weights of the held experts touched,
    held rows in and out: the counters ``moe_routed_rows`` and
    ``moe_groups`` count held rows and held experts only), scaled to the
    kernel calls the trace holds, over their device time.  Two calls an
    expert layer and dispatch, every layer an expert layer."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t, n = _seconds(ctx, GROUPED)
    moe = _moe_counters(ctx)
    if not n or not moe or not moe.get("moe_routed_rows"):
        return None
    dispatches = (ctx.counters.get("serve.decode_dispatches", 0)
                  + ctx.counters.get("serve.prefill_calls", 0))
    calls = 2 * cfg["num_hidden_layers"] * dispatches
    if not calls:
        return None
    c = _counts(ctx, "grouped_matmul_need")
    need, _ = counts.roofline_seconds(
        *c.grouped_matmul_need(cfg, moe["moe_routed_rows"], moe["moe_groups"]),
        peak)
    return 100.0 * need * (n / calls) / t


def serve_gated_attn_decode_roofline(ctx):
    """The visible rows of the traced decode steps, read once for K and
    once for V, over the bandwidth, against ``tdx_decode_attention``'s
    time -- one call an ATTENTION layer and step, so the kernel's calls
    in the trace already count the family's attention layers."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t, n = _seconds(ctx, DECODE_ATTN)
    steps = ctx.counters.get("serve.decode_dispatches")
    if not n or not steps:
        return None
    rows = ctx.counters["serve.decode_rows_sum"] / steps * n  # over all calls
    need, _ = counts.roofline_seconds(
        counts.decode_attention_flops(
            rows, cfg["num_attention_heads"], cfg["head_dim"]),
        counts.decode_attention_bytes(
            rows, cfg["num_key_value_heads"], cfg["head_dim"]), peak)
    return 100.0 * need / t


def serve_moe_rows_held_pct(ctx):
    """Of the router's (token, expert) choices in the window's DECODE
    steps, the share that fell on the experts held here: ``num_experts /
    router_width`` of them where the routing is even (25 at 128 of 512);
    a share that drifts says the routing or the slice is wrong.  The
    decode steps' rows are the slots' own tokens; a prefill's rows are a
    quarter bucket padding, one token id over and over, whose ten choices
    fall inside or outside the share as the seed draws (the window's
    total read 23.8 and 25.7 on two seeds, my chip runs, PR 36)."""
    moe = _moe_counters(ctx)
    if not moe or "moe_rows_elsewhere_decode" not in moe:
        return None
    held = moe.get("moe_routed_rows_decode", 0)
    elsewhere = moe["moe_rows_elsewhere_decode"]
    if not held + elsewhere:
        return None
    return 100.0 * held / (held + elsewhere)
