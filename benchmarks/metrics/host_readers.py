"""Readers of the per-layer metrics that come from the host's clock, the
program's counters and the benchmark's own spans.  A reader takes the
run's context and returns the number, or None where it finds nothing to
read (the harness then leaves the metric out of the line)."""

from __future__ import annotations

from harness import peaks


def _ms(ctx, counter):
    """A counter kept in seconds, in milliseconds; None where it is not."""
    s = ctx.counters.get(counter)
    return None if s is None else 1e3 * s


def materialize_s(ctx):
    return ctx.spans.get("materialize")


def compile_s(ctx):
    return ctx.counters.get("setup.compile_s")


def train_step_ms_p50(ctx):
    return _ms(ctx, "train.step_s_p50")


def train_step_mfu_pct(ctx):
    tokens, window = ctx.counters.get("train.tokens"), ctx.counters.get("train.window_s")
    if not tokens or not window:
        return None
    peak = peaks.peaks(ctx.device_kind)["bf16_flops_per_s"] * ctx.chips
    return 100.0 * ctx.counters["train.flops_per_token"] * tokens / window / peak


def serve_host_syncs_per_token(ctx):
    syncs, tokens = ctx.counters.get("serve.host_syncs"), ctx.counters.get("serve.tokens_generated")
    if not syncs or not tokens:
        return None
    return syncs / tokens


def serve_decode_step_ms_p50(ctx):
    return _ms(ctx, "serve.decode_s_p50")


def serve_prefill_ms_p50(ctx):
    return _ms(ctx, "serve.prefill_s_p50")


def serve_decode_args_ms_p50(ctx):
    """Median of ``serve/decode_args`` (the engine's ``decode_args_s``):
    the host arrays and their transfers before a decode dispatch."""
    return _ms(ctx, "serve.decode_args_s_p50")


def serve_harvest_ms_p50(ctx):
    """Median of ``serve/harvest`` (``harvest_s``): from the end of the
    token block's sync to ``step()``'s return."""
    return _ms(ctx, "serve.harvest_s_p50")


def serve_schedule_ms_per_step(ctx):
    """The host's part of ``serve/schedule`` over the window, a decode
    step: ``serve/prefill`` is its child, so the prefills' dispatch-to-sync
    time is taken out (``schedule_s.total - prefill_s.total``); what stays
    is expiry, admission, a prefill's arguments and first-token
    bookkeeping.  Over the decode dispatches, since one step in several
    admits."""
    total, steps = (ctx.counters.get("serve.schedule_s_total"),
                    ctx.counters.get("serve.decode_dispatches"))
    if not total or not steps:
        return None
    return 1e3 * (total - ctx.counters.get("serve.prefill_s_total", 0.0)) / steps


def serve_step_mfu_pct(ctx):
    flops, window = ctx.counters.get("serve.flops"), ctx.counters.get("serve.window_s")
    if not flops or not window:
        return None
    peak = peaks.peaks(ctx.device_kind)["bf16_flops_per_s"] * ctx.chips
    return 100.0 * flops / window / peak


def serve_ttft_p95_ms(ctx):
    s = ctx.counters.get("serve.ttft_p95_s")
    return None if s is None or s != s else 1e3 * s
