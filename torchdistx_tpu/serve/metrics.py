"""Serving metrics: counters, gauges, and histograms with a plain-dict
snapshot.

Zero-dependency observability for ``serve.engine.ServeEngine`` — the
serving-side sibling of ``utils.profiling`` (which covers the XLA
timeline).  Everything here is host-side bookkeeping: recording a value
never touches the device, so metrics can be sampled every scheduler tick
without perturbing the two-program dispatch discipline.

``snapshot()`` returns one flat JSON-serializable dict (counters verbatim,
gauges verbatim, ``<hist>_mean/_p50/_p95/_max/_count`` per histogram, plus
derived throughput rates) — the record ``scripts/bench_serve.py`` emits as
its last stdout line.
"""

from __future__ import annotations

import functools
import gc
import heapq
import time
from typing import Any, Dict, List, Optional

import numpy as np

__all__ = ["CycleAccount", "Histogram", "ServeMetrics", "latest_metrics"]

#: the ServeMetrics this process made last.  It holds host numbers and a
#: few device scalars, never a cache or a weight, so a reader that
#: outlives the engine (a benchmark's per-layer metric, read after the
#: program is freed) finds the window's counters here
_LATEST: Optional["ServeMetrics"] = None


def latest_metrics() -> Optional["ServeMetrics"]:
    """The most recently constructed :class:`ServeMetrics`, or None."""
    return _LATEST


class _GcClock:
    """What the collector cost this process: the seconds it ran and its
    passes by generation, both running totals that any number of readers
    difference for themselves.  One instance, appended to
    ``gc.callbacks`` once (:func:`_gc_clock`) however many
    :class:`ServeMetrics` are made; a pass stops every thread, so a serve
    cycle is charged the passes that ran inside it whoever set them off."""

    def __init__(self):
        self.seconds = 0.0
        self.passes = [0, 0, 0]
        self._t0: Optional[float] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self.passes[info["generation"]] += 1
            self._t0 = None


_GC_CLOCK = _GcClock()


def _gc_clock() -> _GcClock:
    """The process's collector clock, hooked into ``gc.callbacks`` on
    first use (not at import) and never twice."""
    if _GC_CLOCK not in gc.callbacks:
        gc.callbacks.append(_GC_CLOCK)
    return _GC_CLOCK


class Histogram:
    """Bounded-reservoir histogram of float observations.

    **Window semantics** (read this before putting a quantile on a
    dashboard): ``count`` and ``total`` (hence ``mean``) are exact over
    the histogram's full LIFETIME, but the reservoir keeps only the most
    recent samples — after an overflow compaction it holds between
    ``maxlen // 2`` and ``maxlen`` of them — so ``p50``/``p95``/``max``
    describe a recent window, not all time.  ``window_count`` in
    :meth:`snapshot` says how many samples the quantiles actually saw:
    ``window_count < count`` means the reservoir has wrapped and a p95
    labeled "all-time" would be a misread.  (Serving runs are unbounded;
    all-time exact quantiles are not worth unbounded memory.)
    """

    def __init__(self, maxlen: int = 4096):
        self._maxlen = int(maxlen)
        self._samples: List[float] = []
        self.count = 0
        self.total = 0.0

    def record(self, value: float) -> None:
        value = float(value)
        self.count += 1
        self.total += value
        self._samples.append(value)
        if len(self._samples) > self._maxlen:
            # drop the oldest half in one slice instead of popping per call
            self._samples = self._samples[self._maxlen // 2 :]

    @property
    def window_count(self) -> int:
        """Samples currently in the quantile window (<= ``count``)."""
        return len(self._samples)

    def _quantile(self, q: float) -> Optional[float]:
        if not self._samples:
            return None
        xs = sorted(self._samples)
        idx = min(len(xs) - 1, int(q * (len(xs) - 1) + 0.5))
        return xs[idx]

    def quantile(self, q: float) -> Optional[float]:
        """Windowed quantile (see the class docstring for the window
        semantics) — the public read the SLO engine (``obs/slo.py``)
        and the fleet's per-replica latency summaries evaluate.  None
        while the window is empty."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile must be in [0, 1], got {q}")
        return self._quantile(float(q))

    def snapshot(self) -> Dict[str, Optional[float]]:
        return {
            "count": self.count,
            "mean": self.total / self.count if self.count else None,
            # window stats (see class docstring): quantiles and max look
            # at the last window_count samples only
            "window_count": self.window_count,
            "p50": self._quantile(0.50),
            "p95": self._quantile(0.95),
            "max": max(self._samples) if self._samples else None,
        }


class CycleAccount:
    """The serve loop's account of itself, one record a cycle.

    A *cycle* is the interval between the arrivals on the host of two
    consecutive decode token blocks (the end of the blocking read in
    ``ServeEngine._fetch``).  Every decode dispatch has a running number
    and record ``n`` ends with the arrival of dispatch ``n``'s block.  On
    an engine that reads its tokens a dispatch late the interval holds,
    in order: the first-token waits and the walk of block ``n - 1``, the
    caller's time between two ``step()`` calls, the next step's
    scheduling and arguments, the dispatch of ``n + 1``
    (``dispatched``) and the wait for block ``n``, which the device has
    been computing all the while.

    **What a record holds**, all on ``time.perf_counter`` (the clock of
    ``utils.profiling.timed_annotation``): ``cycle_s``; the SELF seconds
    of each phase that ended inside the cycle (:attr:`KEYS`: a span's
    time less what its child spans cover, so the fields never overlap);
    ``caller_s``, what no phase covers (the time between ``step()``
    calls, and the microseconds between two phases inside one);
    ``cpu_s``, the thread's CPU seconds OUTSIDE the two kinds of wait
    (``time.thread_time`` read at their borders: a runtime may spin while
    it waits); ``gc_s`` and the oldest generation collected;
    ``descheduled_s = cycle_s - wait_s - first_wait_s - cpu_s``, wall
    time in which the thread neither ran nor waited for the device (a
    shared host's signature, and the collector's: its passes run on
    whichever thread set them off); ``admitted`` (requests admitted
    inside it), ``prefills`` (``prefill_s`` records completed inside
    it), ``riders``.  A cycle is *plain* when it completed no prefill:
    under the lag a prefill's device time falls in the cycle AFTER the
    step that admitted and dispatched it, so a wave reads ``admitted
    30`` in one record and ``prefills 30, first_wait_s 0.8`` in the
    next.  ``cpu_s`` is as fine as the kernel's accounting of a thread's
    time: where that ticks every 10 ms (the chip's host does), it and
    ``descheduled_s`` say something of a cycle tens of milliseconds
    long and nothing of a shorter one.

    **What it costs**: a float add a phase, and at an arrival two
    histogram records and a handful of compares.  A record becomes an
    object only when it enters ``slowest`` or ``slowest_plain``, the
    ``SLOWEST`` longest cycles since the metrics began and the longest
    plain ones (the loop's slow-query log: where prefills come in waves
    the longest cycles are all waves, and a stall is a plain one); the
    reservoir is sorted for the running median of the plain cycles every
    ``REFRESH`` of them, never inside each.  A plain cycle longer than
    ``SLOW_FACTOR`` running medians is *slow*: their count and their
    excess seconds over that median are kept as two sums.

    :attr:`starved` counts, by ``prefill`` and ``decode``, the dispatches
    that found the device's queue empty (``ServeEngine._count_if_starved``).
    """

    KEYS = ("schedule", "decode_args", "dispatch", "wait", "first_wait",
            "harvest")
    _WAITS = ("wait", "first_wait")  # the host idle, the device not
    SLOWEST = 16
    SLOW_FACTOR = 2.0
    REFRESH = 256

    def __init__(self, cycle_s: Histogram, cycle_plain_s: Histogram):
        self.cycle_s, self.cycle_plain_s = cycle_s, cycle_plain_s
        self._gc = _gc_clock()
        self._gc_s = self._gc.seconds
        self._gc_passes = list(self._gc.passes)
        self._t0 = time.perf_counter()
        self._arrival: Optional[float] = None
        self._sums = dict.fromkeys(self.KEYS, 0.0)
        # seconds the phases ended so far have covered, children counted
        # once: what a span entered at one reading of it and left at
        # another must take off its own seconds
        self._covered = 0.0
        self._cpu = 0.0
        self._cpu_mark = time.thread_time()
        self._prefills = self._admitted = 0
        self.plain_wait_s = 0.0
        self.plain_p50_s: Optional[float] = None
        self._refresh_at = 16
        self.slow_count = 0
        self.slow_excess_s = 0.0
        self._slowest: list = []  # heaps of (cycle_s, cycle, record)
        self._slowest_plain: list = []
        self.starved = {"prefill": 0, "decode": 0}

    def entered(self, key: str, sink):
        """Called as a phase is entered: the sink its span calls with its
        seconds when it ends, ``sink`` and this account's share."""
        if key in self._WAITS:
            self._cpu += time.thread_time() - self._cpu_mark
        return functools.partial(self._left, key, sink, self._covered)

    def _left(self, key, sink, covered, seconds) -> None:
        sink(seconds)
        own = seconds - (self._covered - covered)
        self._covered += own
        self._sums[key] += own
        if key in self._WAITS:
            self._cpu_mark = time.thread_time()

    def settled(self) -> None:
        """The engine has read everything it had queued and may now sit
        idle: the next block to arrive ends no cycle."""
        self._arrival = None

    def arrived(self, cycle: int, riders: int, dispatched: Optional[int],
                prefills: int, admitted: int) -> None:
        """Dispatch ``cycle``'s token block is on the host.  ``prefills``
        and ``admitted`` are the running counts of completed
        ``prefill_s`` records and of admissions."""
        now = time.perf_counter()
        last, self._arrival = self._arrival, now
        sums, self._sums = self._sums, dict.fromkeys(self.KEYS, 0.0)
        cpu, self._cpu = self._cpu, 0.0
        n_prefills = prefills - self._prefills
        plain = not n_prefills
        n_admitted = admitted - self._admitted
        self._prefills, self._admitted = prefills, admitted
        gc_s, generation = self._gc.seconds - self._gc_s, None
        if gc_s:
            passes = self._gc.passes
            generation = max(
                (g for g in range(3) if passes[g] != self._gc_passes[g]),
                default=None,
            )
            self._gc_s, self._gc_passes = self._gc.seconds, list(passes)
        if last is None:
            return  # the first block since the metrics began, or a settle
        cycle_s = now - last
        self.cycle_s.record(cycle_s)
        if plain:
            self.cycle_plain_s.record(cycle_s)
            self.plain_wait_s += sums["wait"]
            p50 = self.plain_p50_s
            if p50 is not None and cycle_s > self.SLOW_FACTOR * p50:
                self.slow_count += 1
                self.slow_excess_s += cycle_s - p50
            if self.cycle_plain_s.count >= self._refresh_at:
                self.plain_p50_s = self.cycle_plain_s.quantile(0.5)
                self._refresh_at += min(self._refresh_at, self.REFRESH)
        # the longest of all, and the longest of the plain ones apart:
        # where prefills come in waves every one of the former is a wave
        heaps = [
            heap
            for heap in ((self._slowest, self._slowest_plain) if plain
                         else (self._slowest,))
            if len(heap) < self.SLOWEST or cycle_s > heap[0][0]
        ]
        if not heaps:
            return
        record = {"cycle": cycle, "at_s": now - self._t0, "cycle_s": cycle_s,
                  "plain": plain}
        record.update((f"{key}_s", sums[key]) for key in self.KEYS)
        record.update(
            caller_s=cycle_s - sum(sums.values()),
            cpu_s=cpu,
            gc_s=gc_s,
            gc_generation=generation,
            descheduled_s=cycle_s - sums["wait"] - sums["first_wait"] - cpu,
            admitted=n_admitted,
            prefills=n_prefills,
            riders=riders,
            dispatched=dispatched,
        )
        for heap in heaps:
            push = (heapq.heappush if len(heap) < self.SLOWEST
                    else heapq.heappushpop)
            push(heap, (cycle_s, cycle, record))

    def to_json(self) -> dict:
        return {
            "count": self.cycle_s.count,
            "total_s": self.cycle_s.total,
            "plain_count": self.cycle_plain_s.count,
            "plain_total_s": self.cycle_plain_s.total,
            "plain_wait_s": self.plain_wait_s,
            "plain_p50_s": self.plain_p50_s,
            "slow": {
                "factor": self.SLOW_FACTOR,
                "count": self.slow_count,
                "excess_s": self.slow_excess_s,
            },
            "starved_dispatches": dict(self.starved),
            "slowest": [r for _, _, r in sorted(self._slowest, reverse=True)],
            "slowest_plain": [
                r for _, _, r in sorted(self._slowest_plain, reverse=True)
            ],
        }


class ServeMetrics:
    """The ``ServeEngine`` metric set.

    Counters: ``requests_submitted/admitted/completed/truncated``,
    ``tokens_prefilled`` (padded-bucket tokens, the compute actually
    spent), ``tokens_generated`` (every sampled token, the prefill's
    first token included), ``tokens_decoded`` (decode-dispatch tokens
    only — the numerator matching ``decode_s`` time), ``prefill_calls``,
    ``decode_steps`` (on-device decode iterations: ``decode_chunk`` per
    dispatch), ``decode_dispatches`` (compiled-program launches),
    ``host_syncs`` (device->host materializations: one per prefill and
    one per decode dispatch — with ``decode_chunk=K`` roughly 1/K per
    token, THE number the fused decode loop exists to shrink),
    ``masked_slot_steps`` (slot-steps the on-device finish mask threw
    away because a request finished mid-chunk: the wasted-work side of
    the host-sync tradeoff, or rode one more dispatch frozen because the
    host reads its tokens a dispatch late), ``lagged_dispatches`` (decode
    dispatches issued with their predecessor's tokens unread: every one
    in steady state on an engine that lags, 0 on one that reads at once)
    and ``lagged_slot_steps`` (slot-steps a dispatch spent on a slot
    whose finish the host had not yet seen: one per request, the cost of
    the lag; also in ``masked_slot_steps``), the speculative-decoding
    set —
    ``draft_tokens_proposed`` (n-gram draft tokens offered to the
    verifier: ``speculate`` per live slot-iteration),
    ``draft_tokens_accepted`` (drafts that matched the verified greedy
    target and were emitted; ``accepted / proposed`` is the derived
    ``accept_rate``) and ``spec_rejected_lane_steps`` (verify lanes
    discarded by rejection — the speculative twin of
    ``masked_slot_steps``; per live slot-iteration emitting ``e`` tokens
    the identities are exact: ``accepted = e - 1``, ``rejected_lanes =
    speculate + 1 - e``, so ``accepted + rejected_lanes = speculate``) —
    the chunked-prefill set —
    ``chunked_prefills`` (long-prompt admissions split into chunks),
    ``prefill_chunks`` (chunk dispatches those admissions made) and
    ``prefill_interleaved_dispatches`` (decode dispatches interleaved
    between chunks so active slots keep emitting during a long
    admission) — the persistent-loop set —
    ``loop_iterations`` (on-device while_loop iterations across all
    persistent dispatches — equals ``decode_steps`` in persistent mode),
    ``ring_drains`` (loop exits whose output ring the host drained; in
    persistent mode every drain is also exactly one ``host_syncs``
    increment, which is what keeps ``syncs_per_token`` honest),
    ``ring_full_drains`` (drains where the ring filled before every
    slot finished — at least one request spans into the next loop), and
    ``stream_callbacks`` (streamed-tail host callbacks, opt-in) — and
    the prefix-cache set —
    ``prefix_lookup_tokens`` / ``prefix_hit_tokens`` (prompt tokens
    looked up in the radix index vs served from it; their ratio is the
    derived ``prefix_hit_rate``) and ``pages_evicted`` (LRU evictions
    from the prefix index under pool pressure) — and
    ``admissions_rejected_hbm`` (admission ticks the HBM capacity
    planner refused because the projected peak exceeded
    ``ServeEngine(hbm_budget=...)``; the page gate alone would have
    admitted) and ``admissions_rejected_pages`` (ticks the page gate
    refused the FCFS head even after LRU eviction — the page-pressure
    rejection signal the fleet router reads) — and the disaggregation
    set (``ServeEngine.handoff_to``) —
    ``requests_handed_off`` / ``requests_handed_in`` (prefill->decode
    per-request KV handoffs, source/target side),
    ``handoff_pages_moved``, and ``handoff_wire_bytes`` /
    ``handoff_collectives`` (the ring-model cost of those moves, exact
    against the comm audit like ``migration_wire_bytes``).
    Gauges: ``queue_depth``, ``active_slots``, ``slots_free``
    (``num_slots - active_slots``, published first-class for the fleet
    router); paged engines add
    ``pages_in_use`` / ``pages_in_use_hwm`` (current and high-water
    allocated pages), ``num_pages``, and ``pages_free`` (allocatable
    headroom, scratch page excluded); persistent engines add
    ``ring_capacity`` and ``ring_occupancy_hwm`` (high-water loop
    iterations a single dispatch used — at the capacity it means rings
    are filling and requests span drains); speculative engines add the
    ``speculate`` config gauge (drafts per iteration, K); engines that
    know their KV pool footprint add ``kv_cache_bytes`` (total resident
    KV bytes, quantization scales included) and ``kv_bytes_per_token``
    (pool bytes per cache token-row — int8 caches publish roughly half
    the bf16 figure; the rows alone where the cache also holds recurrent
    state) and ``kv_row_bytes`` (one token in one layer that holds
    rows); engines over a state-space model add ``state_slot_bytes``
    (what ONE slot holds of recurrent state, all layers together:
    constant in the context length); quantized (int8) engines additionally publish
    ``kv_quant_err_max`` / ``kv_quant_err_rms`` (observed KV dequant
    error from the numerics-observatory digests; the max is pinned
    ``<= s/2`` by the power-of-two quantizer's round-to-nearest bound).
    All config gauges survive ``reset_metrics()``: the engine re-passes
    them when it rebuilds this object.
    Histograms: ``ttft_s`` (submit -> first token on host),
    ``e2e_latency_s``, ``queue_wait_s``, ``tpot_s`` (per finished
    request: decode seconds per token after the first — the
    time-per-output-token figure, derived from the request's OWN
    lifecycle timestamps so the aggregate and ``RequestResult.tpot_s``
    provably agree), ``slot_occupancy`` (active / total slots, sampled
    per decode dispatch), ``prefill_s`` (one record a prefill: its
    dispatch's host seconds plus the wait for its first token) and
    ``decode_s`` (the span ``serve/decode``: the call of a decode
    dispatch plus the blocked read of a token block, its predecessor's
    on an engine that lags), the two children that split it —
    ``dispatch_s`` (``serve/dispatch``: the host busy in the call and
    the rebinding of its outputs) and ``wait_s`` (``serve/wait``: the
    host blocked on the block, its slack) — and the host phases of a
    tick around them — ``schedule_s`` (expiry + admissions, prefills
    included), ``decode_args_s`` (the decode dispatch's argument list)
    and ``harvest_s`` (the token walk and the gauges after its sync):
    the spans of a profile, for an operator without one.  ``cycle_s``
    is arrival to arrival of two consecutive decode token blocks and
    ``cycle_plain_s`` the cycles in which no prefill was completed;
    :class:`CycleAccount` (``to_json()["cycles"]``) splits each cycle
    into those phases and keeps the slowest whole, and counts the
    ``starved_dispatches``: a count that depends on timing, so it is
    kept there and not among ``counters``, every integer of which the
    session recorder folds into its replay digest.

    Prometheus: :meth:`collector` re-registers this whole set through an
    ``obs.metrics.MetricsRegistry`` (counters -> ``*_total``, gauges
    verbatim, histograms -> summaries with window quantiles — see the
    :class:`Histogram` window note); ``snapshot()``/``to_json()`` stay
    the source of truth and the exposition is a live projection of them.
    """

    _HISTOGRAMS = (
        "ttft_s",
        "e2e_latency_s",
        "queue_wait_s",
        "tpot_s",
        "slot_occupancy",
        "prefill_s",
        "decode_s",
        "schedule_s",
        "decode_args_s",
        "harvest_s",
        "dispatch_s",
        "wait_s",
        "cycle_s",
        "cycle_plain_s",
    )

    def __init__(
        self,
        num_slots: int,
        num_pages: Optional[int] = None,
        ring_capacity: Optional[int] = None,
        speculate: Optional[int] = None,
        kv_cache_bytes: Optional[int] = None,
        kv_bytes_per_token: Optional[int] = None,
        kv_quant_err_max: Optional[float] = None,
        kv_quant_err_rms: Optional[float] = None,
        kv_row_bytes: Optional[int] = None,
        state_slot_bytes: Optional[int] = None,
    ):
        global _LATEST
        _LATEST = self
        self.num_slots = int(num_slots)
        # bytes one slot holds of recurrent state, all layers together
        # (a state-space model; None without such a layer)
        self.state_slot_bytes = (
            state_slot_bytes if state_slot_bytes is None
            else int(state_slot_bytes)
        )
        # bytes one token takes in one layer's cache data: 2 x Hkv x D x
        # itemsize for a (k, v) pair, W x itemsize for a latent row
        self.kv_row_bytes = (
            kv_row_bytes if kv_row_bytes is None else int(kv_row_bytes)
        )
        # counters the serve programs accumulate ON THE DEVICE (an expert
        # model's rows and groups): name -> a device int32 scalar, folded
        # into ``counters`` only when this object is read (``to_json`` /
        # ``snapshot`` / ``sync_device_counters``), never inside step()
        self._device_counters: Dict[str, Any] = {}
        self._device_adds = 0
        self.num_pages = num_pages if num_pages is None else int(num_pages)
        self.ring_capacity = (
            ring_capacity if ring_capacity is None else int(ring_capacity)
        )
        self.speculate = speculate if speculate is None else int(speculate)
        # KV-footprint gauges (quantization-aware): total resident KV pool
        # bytes (data + scales) and the per-token-row cost — int8 caches
        # publish roughly half the bf16 figure, so dashboards can attribute
        # capacity headroom to kv_dtype without re-deriving cache geometry.
        self.kv_cache_bytes = (
            kv_cache_bytes if kv_cache_bytes is None else int(kv_cache_bytes)
        )
        self.kv_bytes_per_token = (
            kv_bytes_per_token
            if kv_bytes_per_token is None
            else int(kv_bytes_per_token)
        )
        # KV dequantization-error gauges (int8 pools only; ISSUE 19):
        # observed max |orig - deq| and its RMS across every
        # quantize-on-write site, harvested from the numerics-observatory
        # digests at existing sync points.  Bounded by s/2 (power-of-two
        # scales, round-to-nearest) — tests/test_kv_quant.py pins the
        # bound.  Like the footprint gauges these survive
        # ``reset_metrics()``: the engine re-passes the current values.
        self.kv_quant_err_max = (
            kv_quant_err_max
            if kv_quant_err_max is None
            else float(kv_quant_err_max)
        )
        self.kv_quant_err_rms = (
            kv_quant_err_rms
            if kv_quant_err_rms is None
            else float(kv_quant_err_rms)
        )
        self.started_at = time.monotonic()
        self.counters: Dict[str, int] = {
            "requests_submitted": 0,
            "requests_admitted": 0,
            "requests_completed": 0,
            "requests_truncated": 0,
            "tokens_prefilled": 0,
            "tokens_generated": 0,
            "tokens_decoded": 0,
            "prefill_calls": 0,
            "chunked_prefills": 0,
            "prefill_chunks": 0,
            "prefill_interleaved_dispatches": 0,
            "decode_steps": 0,
            "decode_dispatches": 0,
            "host_syncs": 0,
            "masked_slot_steps": 0,
            "lagged_dispatches": 0,
            "lagged_slot_steps": 0,
            "draft_tokens_proposed": 0,
            "draft_tokens_accepted": 0,
            "spec_rejected_lane_steps": 0,
            "loop_iterations": 0,
            "ring_drains": 0,
            "ring_full_drains": 0,
            "stream_callbacks": 0,
            "prefix_lookup_tokens": 0,
            "prefix_hit_tokens": 0,
            "pages_evicted": 0,
            "admissions_rejected_hbm": 0,
            "submits_rejected_draining": 0,
            "admissions_rejected_pages": 0,
            "requests_migrated_out": 0,
            "requests_migrated_in": 0,
            "migration_wire_bytes": 0,
            "requests_handed_off": 0,
            "requests_handed_in": 0,
            "handoff_pages_moved": 0,
            "handoff_wire_bytes": 0,
            "handoff_collectives": 0,
        }
        self.queue_depth = 0
        self.active_slots = 0
        self.pages_in_use = 0
        self.pages_in_use_hwm = 0
        self.ring_occupancy_hwm = 0
        self.ttft_s = Histogram()
        self.e2e_latency_s = Histogram()
        self.queue_wait_s = Histogram()
        self.tpot_s = Histogram()
        self.slot_occupancy = Histogram()
        self.prefill_s = Histogram()
        self.decode_s = Histogram()
        self.schedule_s = Histogram()
        self.decode_args_s = Histogram()
        self.harvest_s = Histogram()
        self.dispatch_s = Histogram()
        self.wait_s = Histogram()
        self.cycle_s = Histogram()
        self.cycle_plain_s = Histogram()
        self.cycles = CycleAccount(self.cycle_s, self.cycle_plain_s)

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] += n

    def add_device_counts(self, phase: str, counts: Any) -> None:
        """Accumulate one dispatch's expert-layer counts, a device int32
        ``[rows, groups]`` or ``[rows, groups, elsewhere, overflows]``
        (``nn.moe.tape_totals``), under ``phase``
        (``"prefill"`` | ``"decode"``).  An add on the device: no
        transfer, no sync (but for one fold every 4096 adds)."""
        key = f"moe_{phase}"
        prev = self._device_counters.get(key)
        self._device_counters[key] = counts if prev is None else prev + counts
        # int32 on the device: a long prefill adds ~3e5 rows, so fold
        # into the host's counters (one 8-byte fetch) long before 2**31
        self._device_adds += 1
        if self._device_adds >= 4096:
            self.sync_device_counters()

    def sync_device_counters(self) -> None:
        """Fetch what the device accumulated into ``counters``
        (``moe_routed_rows_<phase>``: (token, expert) rows computed;
        ``moe_groups_<phase>``: experts with at least one row, summed
        over layers and calls) and start the accumulators again."""
        pending, self._device_counters = self._device_counters, {}
        self._device_adds = 0
        for key, value in pending.items():
            phase = key[len("moe_"):]
            # two more counts where the expert layers hold a share of
            # their experts: the rows whose expert is held elsewhere, and
            # the calls that held more rows than their layout is sized
            # for and ran the full-size one
            names = ("moe_routed_rows", "moe_groups", "moe_rows_elsewhere",
                     "moe_layout_overflows")
            for name, n in zip(names, (int(v) for v in np.asarray(value))):
                for full in (name, f"{name}_{phase}"):
                    self.counters[full] = self.counters.get(full, 0) + n

    def cycle_arrived(
        self, cycle: int, riders: int, dispatched: Optional[int]
    ) -> None:
        """Dispatch ``cycle``'s token block has arrived on the host, with
        ``riders`` requests in it; ``dispatched`` is the decode dispatch
        issued since the block before it arrived, if one was
        (:class:`CycleAccount`)."""
        self.cycles.arrived(
            cycle, riders, dispatched,
            self.prefill_s.count, self.counters["requests_admitted"],
        )

    def observe_gauges(self, queue_depth: int, active_slots: int) -> None:
        self.queue_depth = queue_depth
        self.active_slots = active_slots
        self.slot_occupancy.record(active_slots / max(1, self.num_slots))

    def observe_pages(self, in_use: int) -> None:
        """Paged engines only: current allocated pages.  The high-water
        mark accumulates HERE, over this metrics object's lifetime — so
        a reset (e.g. between bench passes) starts a fresh peak instead
        of inheriting the pool's engine-lifetime one."""
        self.pages_in_use = in_use
        self.pages_in_use_hwm = max(self.pages_in_use_hwm, in_use)

    def observe_ring(self, iterations: int) -> None:
        """Persistent engines only: loop iterations one dispatch used.
        Same reset rationale as :meth:`observe_pages` — the high-water
        mark lives on this metrics object, not the engine."""
        self.ring_occupancy_hwm = max(self.ring_occupancy_hwm, iterations)

    def observe_kv_quant(self, err_max: float, err_rms: float) -> None:
        """Quantized engines only: fold one numerics-harvest window's KV
        dequant error into the gauges — running max for the bound check,
        latest-window RMS for the trend line."""
        prev = self.kv_quant_err_max
        self.kv_quant_err_max = (
            float(err_max) if prev is None else max(prev, float(err_max))
        )
        self.kv_quant_err_rms = float(err_rms)

    def to_json(self) -> dict:
        """The one structured, JSON-serializable schema tests, bench, and
        CI all parse: ``{"counters", "gauges", "histograms", "derived"}``
        — counters and gauges verbatim, one summary dict per histogram
        (``count/mean/p50/p95/max``), and the derived rates.
        ``scripts/bench_serve.py`` embeds this whole object per phase
        instead of hand-picking fields."""
        self.sync_device_counters()
        gauges: dict = {
            "queue_depth": self.queue_depth,
            "active_slots": self.active_slots,
            "num_slots": self.num_slots,
            # first-class headroom gauge (additive): the fleet router's
            # load signal, published instead of making every consumer
            # derive num_slots - active_slots
            "slots_free": self.num_slots - self.active_slots,
        }
        if self.num_pages is not None:
            gauges["num_pages"] = self.num_pages
            gauges["pages_in_use"] = self.pages_in_use
            gauges["pages_in_use_hwm"] = self.pages_in_use_hwm
            # allocatable headroom: capacity excludes the reserved
            # scratch page (prefix_cache.PagePool.capacity)
            gauges["pages_free"] = (self.num_pages - 1) - self.pages_in_use
        if self.ring_capacity is not None:
            gauges["ring_capacity"] = self.ring_capacity
            gauges["ring_occupancy_hwm"] = self.ring_occupancy_hwm
        if self.speculate is not None:
            gauges["speculate"] = self.speculate
        if self.kv_cache_bytes is not None:
            gauges["kv_cache_bytes"] = self.kv_cache_bytes
        if self.kv_bytes_per_token is not None:
            gauges["kv_bytes_per_token"] = self.kv_bytes_per_token
        if self.kv_row_bytes is not None:
            gauges["kv_row_bytes"] = self.kv_row_bytes
        if self.state_slot_bytes is not None:
            gauges["state_slot_bytes"] = self.state_slot_bytes
        if self.kv_quant_err_max is not None:
            gauges["kv_quant_err_max"] = self.kv_quant_err_max
        if self.kv_quant_err_rms is not None:
            gauges["kv_quant_err_rms"] = self.kv_quant_err_rms
        wall = time.monotonic() - self.started_at
        # decode-only tokens over decode-only time: prefill's sampled
        # token rides a prefill dispatch, so counting it here would
        # inflate short-generation throughput
        decode_time = self.decode_s.total
        tokens = self.counters["tokens_generated"]
        lookups = self.counters["prefix_lookup_tokens"]
        proposed = self.counters["draft_tokens_proposed"]
        derived = {
            "wall_s": wall,
            "decode_tokens_per_sec": (
                self.counters["tokens_decoded"] / decode_time
                if decode_time > 0
                else None
            ),
            "wall_tokens_per_sec": tokens / wall if wall > 0 else None,
            # the fused-decode headline: device->host round trips per
            # emitted token (1 + 1/max_new at K=1, ~1/K once chunking
            # amortizes them)
            "syncs_per_token": (
                self.counters["host_syncs"] / tokens if tokens > 0 else None
            ),
            # the prefix-cache headline: prompt tokens served from cached
            # pages instead of recomputed
            "prefix_hit_rate": (
                self.counters["prefix_hit_tokens"] / lookups
                if lookups > 0
                else None
            ),
            # the speculative-decode headlines: both EXACT ratios of
            # deterministic counters (so the perf gate can pin them
            # bit-identically), not timings.  proposed = speculate per
            # live slot-iteration, so proposed / speculate is the live
            # slot-iteration count and tokens-per-iteration is
            # 1 + accepted / iterations.
            "accept_rate": (
                self.counters["draft_tokens_accepted"] / proposed
                if proposed > 0
                else None
            ),
            "accepted_tokens_per_iteration": (
                1.0
                + self.counters["draft_tokens_accepted"]
                * self.speculate
                / proposed
                if proposed > 0 and self.speculate
                else None
            ),
        }
        return {
            "counters": dict(self.counters),
            "gauges": gauges,
            "histograms": {
                name: getattr(self, name).snapshot()
                for name in self._HISTOGRAMS
            },
            "derived": derived,
            "cycles": self.cycles.to_json(),
        }

    def snapshot(self) -> dict:
        """``to_json`` flattened to one dict (counters and gauges
        verbatim, ``<hist>_<stat>`` per histogram entry, derived rates) —
        the legacy record shape, kept as a strict projection of
        ``to_json`` so the two can never disagree."""
        j = self.to_json()
        out: dict = dict(j["counters"])
        out.update(j["gauges"])
        for name, summary in j["histograms"].items():
            for k, v in summary.items():
                out[f"{name}_{k}"] = v
        out.update(j["derived"])
        return out

    def collector(self, prefix: str = "tdx_serve"):
        """An ``obs.metrics`` collector over THIS object's live state —
        register with ``registry.register_collector(m.collector(),
        obj=m)`` so a rebound ``engine.metrics`` drops out of the
        exposition when the old object is collected.  Rendering reads
        :meth:`to_json`, so the exposition can never drift from the
        JSON/snapshot schema."""
        import weakref

        from ..obs.metrics import MetricFamily

        # close over a weakref, not self: a registered collector must
        # not pin a rebound engine.metrics object in the exposition
        ref = weakref.ref(self)

        def collect():
            self = ref()
            if self is None:
                return []
            j = self.to_json()
            fams = []
            for name, v in j["counters"].items():
                fams.append(
                    MetricFamily(
                        f"{prefix}_{name}_total", "counter"
                    ).add(v)
                )
            for name, v in j["gauges"].items():
                fams.append(
                    MetricFamily(f"{prefix}_{name}", "gauge").add(v)
                )
            for name, s in j["histograms"].items():
                base = name[:-2] + "_seconds" if name.endswith("_s") else name
                fam = MetricFamily(f"{prefix}_{base}", "summary")
                fam.add(s["p50"], quantile="0.5")
                fam.add(s["p95"], quantile="0.95")
                hist = getattr(self, name)
                fam.add(hist.total, "_sum")
                fam.add(hist.count, "_count")
                fams.append(fam)
                # quantile-window size (Histogram window semantics)
                fams.append(
                    MetricFamily(
                        f"{prefix}_{base}_window_count", "gauge"
                    ).add(s["window_count"])
                )
            starved = MetricFamily(
                f"{prefix}_starved_dispatches_total", "counter"
            )
            for kind, n in j["cycles"]["starved_dispatches"].items():
                starved.add(n, kind=kind)
            fams.append(starved)
            return fams

        return collect
