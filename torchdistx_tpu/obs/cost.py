"""Device cost observatory: per-program XLA cost cards.

The obs/ stack can see host spans (trace), collective wire bytes
(comm), recompiles (recompile), and benchmark history (ledger) — but
until now it was blind to what the compiler actually BUILT: no
per-program FLOP/byte/HBM record existed anywhere, MFU was one
bench-level aggregate, and serve admission gated on free pages with no
idea what a dispatch's temp buffers peak at.  A **CostCard** is that
record: XLA ``cost_analysis()`` FLOPs/bytes-accessed plus
``memory_analysis()`` arg/output/temp/peak bytes for ONE compiled
program, read through ``utils.compat`` (plain dicts; the peak's
source is always named), tagged with the
recompile watcher's scope attribution at capture time.

arXiv:2112.01075 (whose ring cost model ``obs.comm`` implements) is the
grounding for the roofline half: analytic cost models are only useful
once validated against what actually ran — ``flop_attribution`` is
exactly that check (analytic model FLOPs / XLA-counted FLOPs per
program).  arXiv:2004.13336 grounds the capacity half: per-replica
memory accounting is what unlocks sharded weight-update wins, so the
cards' temp/peak bytes feed ``obs.memory.capacity_plan`` — the live
HBM budget the serve engine consults as a second admission gate.

Three exports per card, mirroring the rest of the obs/ stack:

- **Prometheus**: :meth:`CostBook.collector` projects every card as
  ``tdx_cost_*{program=...}`` gauges through any ``obs.metrics``
  registry;
- **Perfetto**: recording a card emits a counter-track sample on the
  PR 4 host-trace timebase (``cost/<program>``), so compile-time cost
  lands on the same timeline as the dispatches that incur it;
- **ledger**: :meth:`CostCard.counter_fields` is what
  ``obs.ledger.ingest_serve_record`` / ``ingest_bench_record`` turn
  into ``metric_class: counter`` rows — XLA flop/byte counts are
  deterministic on a fixed platform, so ``perf_gate.py`` pins them
  EXACTLY (two CPU smoke runs must be bit-identical).

Capture cost: one extra XLA compile per program (``lower().compile()``
does not share the jit call cache's executable on its first use;
repeats are cached).  The serve engine and trainer amortize that into
their warm-up windows; global hooks with unbounded program counts
(chunked replay) stay behind :func:`cards_enabled` (``TDX_COST_CARDS``).
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
from typing import Any, Dict, List, Optional

__all__ = [
    "CostCard",
    "CostBook",
    "compute_cost_card",
    "default_book",
    "cards_enabled",
    "roofline",
    "validate_cost_card",
]

CARD_SCHEMA = "tdx-cost-v1"

#: numeric card fields that are DETERMINISTIC on a fixed platform —
#: what the ledger exports as exact-gating counter rows.  ``peak_bytes``
#: joins only when its source is a compiler analysis (never a runtime
#: watermark, which is load-dependent).
_COUNTER_FIELDS = (
    "flops",
    "bytes_accessed",
    "transcendentals",
    "arg_bytes",
    "out_bytes",
    "temp_bytes",
)


@dataclasses.dataclass
class CostCard:
    """What the compiler built for one program: compile-time FLOP and
    memory-traffic counts (``cost_analysis``) + buffer-assignment sizes
    (``memory_analysis``), with provenance.  ``scope`` is the recompile
    watcher's attribution scope active when the card was captured (the
    same label an in-window compile would be counted under), so a card
    and the recompile counters name programs identically."""

    program: str
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    output_bytes_accessed: Optional[float] = None
    transcendentals: Optional[float] = None
    arg_bytes: Optional[int] = None
    out_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    alias_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    peak_bytes: Optional[int] = None
    peak_source: str = "unavailable"
    scope: Optional[str] = None
    platform: Optional[str] = None
    #: Mosaic (Pallas TPU) kernels in the compiled program: the
    #: ``tpu_custom_call`` count of its HLO.  0 on a TPU means the
    #: program took a jnp path; interpret-mode kernels (CPU) inline into
    #: plain HLO and also count 0.
    pallas_calls: Optional[int] = None
    #: the analytic model's FLOP count for one execution of this program
    #: (e.g. 6N + attention-term per token x tokens per dispatch) — the
    #: numerator of ``flop_attribution``
    analytic_flops: Optional[float] = None

    @property
    def arithmetic_intensity(self) -> Optional[float]:
        if self.flops and self.bytes_accessed:
            return self.flops / self.bytes_accessed
        return None

    @property
    def flop_attribution(self) -> Optional[float]:
        """analytic / XLA-counted FLOPs: ~1.0 means the paper-formula
        cost model describes what the compiler actually built; far off
        means either the model forgot a term (attention, recompute) or
        XLA built something unexpected — the arXiv:2112.01075
        validate-the-analytic-model check, per program."""
        if self.analytic_flops and self.flops:
            return self.analytic_flops / self.flops
        return None

    def counter_fields(self) -> Dict[str, float]:
        """The deterministic numeric fields, prefixed ``cost_`` — the
        ledger's counter rows for this card."""
        out: Dict[str, float] = {}
        for f in _COUNTER_FIELDS:
            v = getattr(self, f)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"cost_{f}"] = v
        if (
            isinstance(self.peak_bytes, (int, float))
            and self.peak_source in ("xla_peak", "arg+out+temp")
        ):
            # a compiler-analysis peak is deterministic; a runtime
            # watermark fallback is load-dependent and must never gate
            out["cost_peak_bytes"] = self.peak_bytes
        return out

    def to_json(self) -> dict:
        d = {"schema": CARD_SCHEMA}
        d.update(dataclasses.asdict(self))
        d["arithmetic_intensity"] = self.arithmetic_intensity
        d["flop_attribution"] = self.flop_attribution
        return d


#: TDX_COST_CARDS spellings that mean OFF — ONE list for both probes,
#: so the kill switch can never half-engage
_OFF_VALUES = ("0", "false", "")


def _env_state() -> Optional[bool]:
    """TDX_COST_CARDS as a tri-state: None (unset), True (on), False
    (any off spelling, case-insensitive)."""
    v = os.environ.get("TDX_COST_CARDS")
    if v is None:
        return None
    return v.strip().lower() not in _OFF_VALUES


def cards_enabled(default: bool = False) -> bool:
    """The global opt-in for cost-card capture at UNBOUNDED hook sites
    (chunked-replay chunk compiles).  Bounded-program components (the
    serve engine's per-bucket/per-K programs, the trainer's one step)
    take an explicit constructor flag instead and default ON —
    ``TDX_COST_CARDS=0`` force-disables those too."""
    state = _env_state()
    return default if state is None else state


def force_disabled() -> bool:
    """True when ``TDX_COST_CARDS`` is explicitly set to an off
    spelling — the kill switch that turns EVERY capture site off
    (compile-cost-sensitive runs)."""
    return _env_state() is False


def compute_cost_card(
    fn: Any,
    *args: Any,
    name: str,
    analytic_flops: Optional[float] = None,
    book: Optional["CostBook"] = None,
    **kwargs: Any,
) -> CostCard:
    """The one lower/compile/cost_analysis dance (``utils.profiling.
    cost_summary`` delegates here).  ``fn`` may be jitted or plain;
    nothing executes — the program is lowered and compiled only, so
    donated-argument buffers are safe to pass (lowering reads avals,
    never contents; capture a card BEFORE the dispatch that consumes
    them).  The card's ``scope`` records the recompile-scope label
    active at the call site; the capture's own compile runs under a
    ``cost_card/<name>`` scope so watchers attribute it, never confuse
    it with a dispatch-path recompile.  With ``book`` the card is also
    recorded (Perfetto counter sample included)."""
    import jax

    from ..utils import compat
    from .recompile import current_scope, recompile_scope

    card = CostCard(
        program=name,
        scope=current_scope(),
        analytic_flops=analytic_flops,
    )
    try:
        card.platform = jax.devices()[0].platform
    except Exception:
        pass
    if hasattr(fn, "lower"):
        jitted = fn
    else:
        # wrap rather than jit the callable directly: step-class
        # instances (ShardedTrainStep and friends define __eq__) are
        # unhashable, and jit requires a hashable callable
        jitted = jax.jit(lambda *a, **kw: fn(*a, **kw))
    with recompile_scope(f"cost_card/{name}"):
        compiled = jitted.lower(*args, **kwargs).compile()
    card.pallas_calls = compiled.as_text().count(
        'custom_call_target="tpu_custom_call"'
    )
    ca = compat.compiled_cost_analysis(compiled)
    if ca:
        card.flops = _num(ca.get("flops"))
        card.bytes_accessed = _num(ca.get("bytes accessed"))
        card.output_bytes_accessed = _num(ca.get("bytes accessed output"))
        card.transcendentals = _num(ca.get("transcendentals"))
    ma = compat.compiled_memory_analysis(compiled)
    if ma:
        for key in (
            "arg_bytes",
            "out_bytes",
            "temp_bytes",
            "alias_bytes",
            "generated_code_bytes",
            "peak_bytes",
        ):
            if key in ma:
                setattr(card, key, ma[key])
        card.peak_source = ma["peak_source"]
    else:
        # no compiler memory analysis on this jax/backend: fall back to
        # the runtime watermark, and SAY so — a load-dependent number
        # must never be mistaken for a per-program property (it is also
        # excluded from the deterministic counter_fields)
        from .memory import hbm_watermark

        wm = hbm_watermark()
        card.peak_bytes = wm.get("peak_bytes")
        card.peak_source = f"hbm_watermark:{wm.get('source')}"
    if book is not None:
        book.record(card)
    return card


def _num(v) -> Optional[float]:
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return f if math.isfinite(f) else None


class CostBook:
    """Per-program card store: the queryable runtime surface ("what did
    the compiler build for serve/decode/k4?") plus the Prometheus
    projection.  Thread-safe; recording re-emits the card's Perfetto
    counter sample (no-op unless tracing is enabled), so a book is also
    the counter-track feeder."""

    def __init__(self) -> None:
        self._cards: Dict[str, CostCard] = {}
        self._lock = threading.Lock()

    def record(self, card: CostCard) -> CostCard:
        with self._lock:
            self._cards[card.program] = card
        from .trace import get_tracer

        get_tracer().counter(
            f"cost/{card.program}",
            flops=float(card.flops or 0.0),
            bytes_accessed=float(card.bytes_accessed or 0.0),
            peak_bytes=float(card.peak_bytes or 0.0),
        )
        return card

    def get(self, program: str) -> Optional[CostCard]:
        with self._lock:
            return self._cards.get(program)

    def cards(self) -> Dict[str, CostCard]:
        with self._lock:
            return dict(self._cards)

    def __len__(self) -> int:
        with self._lock:
            return len(self._cards)

    def max_temp_bytes(self) -> int:
        """The worst per-program temp footprint on record — what the
        capacity planner charges as transient dispatch overhead (the
        programs run serially, so the max, not the sum)."""
        return max(
            (c.temp_bytes or 0 for c in self.cards().values()), default=0
        )

    def max_peak_bytes(self) -> int:
        return max(
            (
                c.peak_bytes or 0
                for c in self.cards().values()
                if c.peak_source in ("xla_peak", "arg+out+temp")
            ),
            default=0,
        )

    def to_json(self) -> Dict[str, dict]:
        """``{program: card}`` — what bench phase records embed under
        ``cost_cards`` (and the ledger adapters read back)."""
        return {
            name: card.to_json()
            for name, card in sorted(self.cards().items())
        }

    def collector(self, prefix: str = "tdx_cost"):
        """An ``obs.metrics`` collector over the book: one labeled
        sample per card for flops / bytes-accessed / temp / peak (the
        peak family carries its source label — see
        ``compiled_memory_analysis`` on why that is not optional)."""
        import weakref

        from .metrics import MetricFamily

        ref = weakref.ref(self)  # never pin a discarded engine's book

        def collect():
            book = ref()
            if book is None:
                return []
            cards = book.cards()
            if not cards:
                return []
            fams = []
            specs = (
                ("flops", "flops", "XLA-counted FLOPs per execution"),
                ("bytes_accessed", "bytes_accessed",
                 "XLA-counted bytes accessed per execution"),
                ("temp_bytes", "temp_bytes",
                 "buffer-assignment temp bytes"),
            )
            for field, suffix, help_ in specs:
                fam = MetricFamily(f"{prefix}_{suffix}", "gauge", help_)
                for name in sorted(cards):
                    v = getattr(cards[name], field)
                    if v is not None:
                        fam.add(v, program=name)
                if fam.samples:
                    fams.append(fam)
            peak = MetricFamily(
                f"{prefix}_peak_bytes", "gauge",
                "per-program peak bytes (source labeled)",
            )
            for name in sorted(cards):
                c = cards[name]
                if c.peak_bytes is not None:
                    peak.add(
                        c.peak_bytes, program=name, source=c.peak_source
                    )
            if peak.samples:
                fams.append(peak)
            return fams

        return collect


_DEFAULT: Optional[CostBook] = None
_DEFAULT_LOCK = threading.Lock()


def default_book() -> CostBook:
    """Process-wide book for components without a natural owner (the
    trainer's step program, replay chunks).  Engine-owned books
    (``ServeEngine.cost_book``) stay separate so two engines' programs
    never collide."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = CostBook()
        return _DEFAULT


def roofline(
    card: CostCard,
    *,
    peak_flops: Optional[float] = None,
    hbm_bw: Optional[float] = None,
) -> dict:
    """Roofline classification of one program: compute-bound floor
    (``flops / peak_flops``), memory-bound floor (``bytes_accessed /
    hbm_bw``), and which bound dominates.  Pass the chip's numbers
    (v5e bf16: 197e12 FLOP/s, ~819e9 B/s); on hosts where they are
    meaningless (the CPU test mesh) call without them and get the raw
    counts only."""
    out: dict = {
        "flops": card.flops,
        "bytes_accessed": card.bytes_accessed,
        "arithmetic_intensity": card.arithmetic_intensity,
    }
    cb = mb = None
    if peak_flops and card.flops:
        cb = card.flops / peak_flops
        out["compute_bound_s"] = cb
    if hbm_bw and card.bytes_accessed:
        mb = card.bytes_accessed / hbm_bw
        out["memory_bound_s"] = mb
    if cb is not None and mb is not None:
        out["bound"] = "compute" if cb >= mb else "memory"
    return out


def span_mfu(
    card: CostCard,
    *,
    executions: int,
    seconds: Optional[float],
    peak_flops: Optional[float],
) -> Optional[float]:
    """Measured MFU of one program's span: XLA-counted FLOPs x how many
    times it ran, over the span's wall seconds and the chip peak — the
    per-span replacement for the single end-of-run MFU number.  None
    when any input is missing (no peak on CPU, no time recorded)."""
    if not (card.flops and executions and seconds and peak_flops):
        return None
    return card.flops * executions / (seconds * peak_flops)


def validate_cost_card(card, where: str = "card") -> List[str]:
    """Schema errors for one serialized card (empty list == valid) —
    the ``check_obs_artifacts.py --cost`` contract."""
    errs: List[str] = []
    if not isinstance(card, dict):
        return [f"{where}: not an object"]
    if card.get("schema") != CARD_SCHEMA:
        errs.append(f"{where}: bad schema {card.get('schema')!r}")
    if not card.get("program") or not isinstance(card.get("program"), str):
        errs.append(f"{where}: missing str 'program'")
    for key in ("flops", "bytes_accessed"):
        v = card.get(key)
        if v is None:
            errs.append(f"{where}: missing {key}")
        elif isinstance(v, bool) or not isinstance(v, (int, float)):
            errs.append(f"{where}: non-numeric {key}: {v!r}")
        elif not math.isfinite(v) or v < 0:
            errs.append(f"{where}: bad {key}: {v!r}")
    src = card.get("peak_source")
    if not isinstance(src, str) or not src or src == "unavailable":
        errs.append(f"{where}: peak_bytes source not named ({src!r})")
    elif card.get("peak_bytes") is None:
        errs.append(f"{where}: peak_source {src!r} without peak_bytes")
    return errs
