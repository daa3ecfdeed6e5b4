"""Readers of the serve loop's own account of its cycles (PR 38): what
``torchdistx_tpu.serve.metrics.CycleAccount`` and the counters beside it
kept over the window, read from ``latest_metrics()`` (the metrics object
outlives the engine; it holds numbers, never a cache or a weight).

A *cycle* is the interval between the arrivals on the host of two
consecutive decode token blocks; it is *plain* when no prefill was
completed inside it.  All on the program's clock (``time.perf_counter``,
the one its spans use) and over every cycle of the window, not the
traced seconds.  A reader that finds nothing to read -- a program
without the account, a window without a plain cycle -- returns None,
never 0."""

from __future__ import annotations


def _metrics():
    try:
        from torchdistx_tpu.serve.metrics import latest_metrics
    except ImportError:
        return None
    return latest_metrics()


def _plain(m):
    """``(account, seconds of the plain cycles)``, or None without either."""
    account = getattr(m, "cycles", None)
    hist = getattr(m, "cycle_plain_s", None)
    if account is None or hist is None or not hist.total:
        return None
    return account, hist.total


def serve_decode_cycle_ms_p50(ctx):
    """The decode step as the program sees it: the median plain cycle."""
    hist = getattr(_metrics(), "cycle_plain_s", None)
    p50 = None if hist is None else hist.quantile(0.5)
    return None if p50 is None else 1e3 * p50


def serve_host_busy_pct(ctx):
    """Of the plain cycles' seconds, the share the host did NOT spend
    blocked on the token block (``serve/wait``): its phases, the caller
    and the dispatch call itself.  The host sets the pace as this nears
    100."""
    found = _plain(_metrics())
    if found is None:
        return None
    account, total = found
    return 100.0 * (1.0 - account.plain_wait_s / total)


def serve_starved_dispatch_pct(ctx):
    """Of the window's prefill and decode dispatches, the share that
    found the device's queue empty (the last program queued had ended)."""
    m = _metrics()
    starved = getattr(getattr(m, "cycles", None), "starved", None)
    counters = getattr(m, "counters", {})
    dispatches = (counters.get("decode_dispatches", 0)
                  + counters.get("prefill_calls", 0))
    if starved is None or not dispatches:
        return None
    return 100.0 * sum(starved.values()) / dispatches


def serve_prefill_share_pct(ctx):
    """Of all the cycles' seconds, the share that was prefills
    (``prefill_s``: since PR 35 a prefill's record is its device time)."""
    m = _metrics()
    cycles, prefills = getattr(m, "cycle_s", None), getattr(m, "prefill_s", None)
    if cycles is None or prefills is None or not cycles.total:
        return None
    return 100.0 * prefills.total / cycles.total


def serve_slow_cycle_share_pct(ctx):
    """Of the plain cycles' seconds, the excess of the slow ones (longer
    than twice the running median) over that median: what stalls cost."""
    found = _plain(_metrics())
    if found is None:
        return None
    account, total = found
    return 100.0 * account.slow_excess_s / total


def serve_lagged_slot_steps_pct(ctx):
    """Of the slot-steps the decode dispatches ran, the share spent on a
    slot whose finish the host had not yet seen: the lag's own cost."""
    m = _metrics()
    counters = getattr(m, "counters", {})
    lagged, steps = counters.get("lagged_slot_steps"), counters.get("decode_steps")
    if lagged is None or not steps:
        return None
    return 100.0 * lagged / (steps * m.num_slots)
