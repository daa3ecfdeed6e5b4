"""Readers of the Jamba family's per-layer metrics: its own kernels
(``tdx_selective_scan``, ``tdx_selective_state_update``), the decode
attention kernel at the family's TWO attention layers (the generic
``serve.decode_attn_roofline`` multiplies by ``num_hidden_layers``, which
is wrong for a hybrid stack), and the share of a prefill's rows that is
bucket padding.

What a call needs comes from the family's counts
(``families/jamba_counts.py``: operations and bytes from the shapes and
the configuration's stated dtypes), the device time from the trace, the
tokens from the benchmark's own count of the window and from the
program's counters (``serve.metrics.latest_metrics()``: the metrics
object outlives the engine; it holds numbers, never a cache or a
weight).  A reader that finds nothing to read -- a program without that
kernel, as the parent of the PR that brought them -- returns None, never
0.  No share can read above 100: the needs count the true tokens and
the slots that decoded, the kernels work the bucket's rows and every
slot."""

from __future__ import annotations

from harness import counts, peaks, tracered

SCAN = "tdx_selective_scan"
UPDATE = "tdx_selective_state_update"
DECODE_ATTN = "tdx_decode_attention"


def _seconds(ctx, kernel: str):
    """Device time and count of the operations that carry the kernel's
    name: the Mosaic call itself (tag ``pallas``), or the ``fusion`` the
    compiler wraps around it under the call's own name -- a prefill's
    ``tdx_selective_scan`` is fused with the write of its final state
    into the slab (``kind=kCustom``: the trace then shows
    ``tdx_selective_scan.N = ... fusion(...)``, my chip run, PR 34)."""
    if ctx.reduction is None:
        return 0.0, 0
    return tracered.kernel_seconds(
        ctx.reduction["ops"],
        lambda name, tag: (tag.startswith(("pallas", "fusion"))
                           and tracered.base_name(name) == kernel))


def _counts(ctx, *names):
    return ctx.family(*("counts." + n for n in names)).counts


def serve_state_update_roofline(ctx):
    """The state of the slots that decoded a token, read once and
    written once, with their row operands, over the bandwidth (or the
    recurrence's operations over the peak, whichever is larger), against
    the kernel's device time: one call a Mamba layer and decode step.
    The slots that decoded come from the benchmark's own count of the
    window (tokens delivered less the prompts' first tokens, a step)."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t, n = _seconds(ctx, UPDATE)
    steps = ctx.counters.get("serve.decode_dispatches")
    if not n or not steps:
        return None
    decoded = ctx.counters["serve.tokens"] - len(ctx.counters["serve.prompt_lens"])
    c = _counts(ctx, "state_update_need")
    need, _ = counts.roofline_seconds(
        *c.state_update_need(cfg, decoded / steps), peak)
    return 100.0 * n * need / t


def serve_selective_scan_roofline(ctx):
    """The recurrence over the TRUE prompt lengths (not the padded
    bucket) against ``tdx_selective_scan``'s time: the window's mean
    prompt stands for each traced call (one a Mamba layer and
    prefill)."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t, n = _seconds(ctx, SCAN)
    lens = ctx.counters.get("serve.prompt_lens")
    if not n or not lens:
        return None
    c = _counts(ctx, "selective_scan_need")
    per_prompt = [counts.roofline_seconds(*c.selective_scan_need(cfg, p), peak)[0]
                  for p in lens]
    return 100.0 * n * (sum(per_prompt) / len(per_prompt)) / t


def serve_mqa_decode_attn_roofline(ctx):
    """``serve.decode_attn_roofline`` for a stack whose attention layers
    are a few of many: the visible rows of the traced decode steps, read
    once for K and once for V, over the bandwidth, against
    ``tdx_decode_attention``'s time -- one call an ATTENTION layer and
    step, so the traced steps are the calls over the family's count of
    attention layers."""
    cfg, peak = ctx.cell.config, peaks.peaks(ctx.device_kind)
    t, n = _seconds(ctx, DECODE_ATTN)
    steps = ctx.counters.get("serve.decode_dispatches")
    if not n or not steps:
        return None
    c = _counts(ctx, "layer_split", "head_dim")
    rows = ctx.counters["serve.decode_rows_sum"] / steps * n  # over all calls
    need, _ = counts.roofline_seconds(
        counts.decode_attention_flops(
            rows, cfg["num_attention_heads"], c.head_dim(cfg)),
        counts.decode_attention_bytes(
            rows, cfg["num_key_value_heads"], c.head_dim(cfg)), peak)
    return 100.0 * need / t


def serve_scan_pad_share_pct(ctx):
    """Of the rows the window's prefills worked, the share that was
    bucket padding: ``1 - true prompt tokens / rows dispatched``.  A
    recurrence works its bucket's rows whatever the prompt's length (it
    masks them, it does not skip them, but for whole chunks).  The rows
    are the program's counter ``tokens_prefilled`` (which counts the
    BUCKET of every prefill dispatched), the true tokens the
    benchmark's own count of the window's prompts."""
    try:
        from torchdistx_tpu.serve.metrics import latest_metrics
    except ImportError:
        return None
    m = latest_metrics()
    lens = ctx.counters.get("serve.prompt_lens")
    rows = None if m is None else m.counters.get("tokens_prefilled")
    if not rows or not lens:
        return None
    return 100.0 * (1.0 - sum(lens) / rows)
