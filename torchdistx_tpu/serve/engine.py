"""``ServeEngine``: continuous-batching inference over a slot KV cache.

The eager-API-over-compiled-step split: the public surface
(``submit(prompt, ...) -> RequestHandle``, ``step()``, ``run(requests)``)
is plain host Python — queueing, slot assignment, deadline bookkeeping —
while ALL device work flows through exactly two jitted programs:

1. **Prefill** (one per padded bucket length): run one request's prompt —
   padded up to the bucket — through the model's existing
   ``forward_cached`` against a fresh single-request cache, sample the
   first token from the last REAL prompt position, and
   ``dynamic_update_slice`` the prefilled slab into the request's slot row
   of the engine cache (``kv_cache.write_slot``).
2. **Decode** (one per ``decode_chunk`` value — default a single one): a
   ``lax.scan`` of ``K = decode_chunk`` fused batched steps over ALL
   slots — each row at its own cache depth (``forward_decode`` /
   ``ops.attention.slot_cached_attention``, which routes to the pallas
   slot-paged kernel on TPU), per-slot temperature (a dynamic input: any
   greedy/sampling mix shares the program), carrying the donated KV slab
   and an on-device finished mask (``generation._make_fused_decode``).
   One dispatch and ONE host sync emit ``K x num_slots`` tokens; with
   the default ``decode_chunk=1`` this is exactly the classic
   one-token-per-sync decode step.

**Persistent mode** (``decode_mode="persistent"``): the decode program
becomes ONE ``lax.while_loop`` over the same fused body
(``generation._make_persistent_decode``) that runs until every slot's
finish bit is set or the device-resident output ring
(``(ring_capacity, num_slots)`` tokens + per-iteration valid mask +
write cursor) fills.  The host crosses the device boundary once per
*generation wave*, not once per K tokens: prefill defers its
first-token fetch (the pending device scalar rides along with the next
ring drain), the drain is the ONE sync (``host_syncs`` counts exactly
the drains, keeping ``syncs_per_token`` honest — ~0), and
``_check_finished`` walks the drained ring with the very rules the
device applied, exactly as it walks the fused ``(K, B)`` block.
Admission/prefill batch at loop exits, so the scheduler's granularity
coarsens from the chunk to the loop; retire-to-scratch still holds
because pages are only ever freed/reallocated at those same loop
boundaries — a frozen slot's in-loop writes go through the table row
the loop was dispatched with, which names the slot's own pages (or
scratch) for the loop's whole lifetime.  The K-step ``chunked`` path
stays the pinned-bit-identical reference (streams are identical by
construction: one shared body, one sampler key schedule).

**A dispatch's small arguments** are HOST arrays, and the program call
makes the transfers (inside the spans ``serve/decode`` and
``serve/prefill``): the step path converts nothing beforehand.  A decode
dispatch's seven per-slot inputs are one packed ``(7, num_slots)`` int32
array (``generation.pack_slot_state``, unpacked on the device bit for
bit), written once in ``_decode_args`` for all four decode programs; the
span ``serve/decode_args`` holds that packing, the copies of the
speculative history and the page tables, and the cost-card lookup — no
transfer, except the persistent loop's, whose state goes to the device
first so that deferred first tokens can be spliced into it there.  A
prefill's are the padded prompt and NumPy scalars with their dtypes
written out.  Every array handed over is fresh or a copy: the host
mirrors are written again in ``serve/harvest``.  Two arguments are no
transfer, because they only ever travel from one program to the next on
the device: the fused one-token program's final carry, which the next
dispatch starts from, and the vector every prefill program writes its
sampled token into.

**Tokens are read one dispatch late** where that carry exists and
prefills are whole (``ServeEngine._lags``: what every default gives):
``step()`` k issues decode ``D(k)`` behind ``D(k-1)``, which is still
running, and only then fetches and walks ``D(k-1)``'s block, over the
requests and slots ``D(k-1)`` was dispatched with.  The host overrides
the carry only for the slots it changed since (one more row of the
packed state).  ``step``'s docstring says what a caller sees;
``_settle()`` (fetch and walk whatever is in flight) is what ``drain``,
``migrate_to`` / ``handoff_to``, ``step_prefill`` and the end of ``run``
call before they read the mirrors or the cache.  Every other engine
settles right after each dispatch: the same step.

**The step path is written once**, whatever the options: one
``_decode_step`` (``serve/decode_args`` → ``serve/decode`` →
``serve/harvest``), for which the four decode programs differ by a row
of ``_DECODE_VARIANTS`` (the builder, the outputs fetched, how the fetch
reads as tokens and counts per iteration and slot, whether a carry comes
back), and one
``_dispatch_prefill`` (``serve/prefill`` per chunk), for which whole
against chunked is the length of a chunk list and slab against paged,
cold against warm, is ``_prefill_call``'s choice of program and
arguments.  docs/serving.md has the table.

Admitting or retiring a request changes only tiny dynamic inputs
(positions, temperatures, budgets, a slot index), never a compiled
shape — the jit cache stays at two programs (plus one per extra bucket
actually used) no matter how traffic churns.  With ``decode_chunk > 1``
admission happens only at chunk boundaries: a slot freed at in-chunk
step ``j`` idles for the remaining ``K - 1 - j`` slot-steps (masked
on-device, surfaced as the ``masked_slot_steps`` counter) and is refilled
on the next ``step()``.  The aim is ~K fewer host syncs per token; a
greedy slot's token stream is bit-identical to
``generation.generate`` on that prompt alone, for every ``decode_chunk``
(pinned in tests/test_serve.py).

**What a slot holds is the model's to say**, layer by layer
(``kv_cache.entry_kind``): KV rows as a ``(k, v)`` pair, a latent row
(``LatentEntry``, multi-head latent attention), or a recurrent state
with no rows at all (``RecurrentState``, a state-space layer: written
whole by the prefill, rewritten whole for every slot by every decode
step).  The step path is the same for all of them — the programs carry
the cache as a pytree and ``write_slot`` / the models' own
``forward_decode`` know the kinds; what is NOT the same is the set of
options built over each kind: ``_SLAB_ONLY`` names, for a latent cache
and for recurrent state, what the constructor (and ``migrate_to`` /
``handoff_to``) refuse, and why.

Sampling (``generation._make_slot_sampler``) reuses ``generate``'s
top-k/top-p filters; the two jitted programs live in the model's
``generation._cached_jit`` store so executables are collected with the
model.

**Paged mode** (``page_size=N``): the device cache becomes per-layer
page pools ``(num_pages, page_size, Hkv * D)`` with host page tables
(``serve/kv_cache.py``) and a refcounted radix prefix index
(``serve/prefix_cache.py``).  Admission additionally gates on free
pages (a request claims only its page-aligned ``prompt +
max_new_tokens`` footprint, minus whatever prefix the index already
holds); prefill computes only the uncached suffix against a
page-table gather of the slot's logical cache and scatters just the
suffix rows back; retire decrements page refcounts and full-prompt
pages live on in the index until LRU eviction.  The dispatch
discipline is unchanged — prefill programs split cold (static
``cache_pos=0``, flash-capable) / warm (traced prefix length), decode
stays the one fused scan with the tiny int32 page table as an extra
dynamic input — and greedy streams are bit-identical to the
contiguous (``page_size=None``) engine (tests/test_serve.py).
"""

from __future__ import annotations

import contextlib
import functools
import time
from collections import deque
from typing import Any, Iterable, List, NamedTuple, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import io_callback

from ..obs.blackbox import resolve_record
from ..obs.comm import record_collective
from ..obs.cost import CostBook, force_disabled as _cost_force_disabled
from ..obs.numerics import (
    NumericsBook,
    numerics_enabled,
    numerics_tape,
    tap,
)
from ..obs.trace import get_tracer, request_trace_events

from ..generation import (
    FIRST_ON_DEVICE,
    FROM_HOST,
    SLOT_STATE_ROWS,
    _NUMERICS_SITES,
    _cached_jit,
    _check_sampling_args,
    _make_fused_decode,
    _make_fused_spec_decode,
    _make_persistent_decode,
    _make_persistent_spec_decode,
    _make_slot_sampler,
    pack_slot_state,
)
from ..nn.module import functional_call
from ..nn.moe import moe_count_tape, tape_totals
from ..utils.compat import jit_cache_size
from ..utils.profiling import timed_annotation
from .kv_cache import (
    LATENT,
    STATE,
    PagedKVCache,
    SlotKVCache,
    cache_kinds,
    canonicalize_kv_dtype,
    heads_view,
    paged_scatter_rows,
    paged_view,
    write_slot,
)
from .metrics import ServeMetrics
from .prefix_cache import PagePool, RadixPrefixIndex
from .scheduler import Request, RequestHandle, RequestResult, Scheduler

__all__ = ["ServeEngine"]


def _taped(num_on: bool, body):
    """Trace ``body()`` (a tuple-returning program body) under a
    declared-site numerics tape when the engine's observatory is on,
    appending the ``{site: digest}`` dict as ONE extra program output —
    digests ride the same dispatch and materialize with the same sync.
    With ``num_on=False`` the body traces byte-identically to the
    pre-observatory program (``tap`` calls inside it are identities)."""
    if not num_on:
        return body()
    with numerics_tape(sites=_NUMERICS_SITES) as tape:
        out = body()
    return out + (tape.digests(),)


def _cache_sharding(
    params: dict,
    mesh=None,
    tp_axis: str = "tp",
    kv_heads: Optional[int] = None,
    plan=None,
):
    """Device placement for the slot/paged KV cache.

    With a ``plan`` (a :class:`~torchdistx_tpu.parallel.plan.ShardingPlan`)
    the pool layout comes from the plan's ``kv_cache`` pseudo-path rule
    when one matches (``llama_tp_plan`` carries it), so the serve pool and
    the training-side annotations are the same declarative object; the
    ``kv_heads % tp`` divisibility assertion still gates below either way.

    With a ``mesh`` the policy is the **head-axis sharding**: every cache
    array is stored ``(num_slots | num_pages, rows, Hkv * D)`` (scales:
    ``Hkv``; ``serve/kv_cache.py``), and ``NamedSharding(mesh, P(None,
    None, tp_axis))`` splits the merged tail into contiguous ``Hkv / tp``
    head groups, co-locating each device's group with the Megatron
    column shards (``wq``/``wk``/``wv``) that produce it — attention then
    partitions along heads under GSPMD with no cache collective at all,
    and each device holds ``1/tp`` of the KV footprint (which is what
    ``memory_plan()`` admits against).  ``kv_heads % tp`` is asserted
    here with a named error: an uneven split would make GSPMD pad or
    replicate the head axis, silently devouring the HBM the sharding
    exists to save.  A plan's ``kv_cache`` rule may also be written
    against the model's ``(lead, rows, Hkv, D)`` layout: its head entry
    is mapped onto the merged axis (``head_dim`` cannot be split
    separately — the stored array has no such axis).

    REPLICATED is the *fallback*, not the policy: with no mesh but
    sharded params (e.g. FSDP-materialized weights passed via
    ``params=``), the cache is replicated over the params' mesh (a cache
    committed to one device against mesh-committed params is an
    incompatible-devices jit error); with single-device params, the
    default device.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is not None:
        tp = int(mesh.shape[tp_axis])
        if kv_heads is None:
            raise ValueError(
                "cannot head-shard the KV cache: the model's config "
                "exposes no KV head count (n_kv_heads / n_heads / "
                "n_head) — pass mesh=None to serve it replicated"
            )
        if kv_heads % tp != 0:
            raise ValueError(
                f"KV cache head axis (n_kv_heads={kv_heads}) does not "
                f"divide over the '{tp_axis}' mesh axis ({tp} devices): "
                f"{kv_heads} % {tp} != 0.  Pick a tp degree that divides "
                "n_kv_heads (or a model with more KV heads) — an uneven "
                "split would silently replicate the head axis"
            )
        spec = None
        if plan is not None:
            spec = plan.maybe_spec_for("kv_cache", (0, 0, kv_heads))
        if spec is None:
            spec = PartitionSpec(None, None, tp_axis)
        elif len(spec) > 3:
            if any(p is not None for p in spec[3:]):
                raise ValueError(
                    f"the plan's kv_cache rule {spec} shards head_dim: "
                    "the KV cache is stored (lead, rows, Hkv * D) and "
                    "splits over heads only"
                )
            spec = PartitionSpec(*spec[:3])
        return NamedSharding(mesh, spec)
    for leaf in jax.tree_util.tree_leaves(params):
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding):
            return NamedSharding(sh.mesh, PartitionSpec())
    return None


#: cache kinds that only the slab cache and the default programs serve,
#: and how a refusal names each.  Why, by mechanism -- a latent row has
#: no head axis for int8 scales or a TP split, and its warm / paged /
#: speculative programs are not written; a recurrent state has no rows
#: at all: a page or a shared prefix would need a state SNAPSHOT at the
#: page's edge, a rejected draft a ROLLBACK of the state, a warm chunk a
#: program that carries the state in, a TP mesh a split of ``d_inner``;
#: and a move between engines (``migrate_to`` / ``handoff_to``) or a
#: replayed session would copy a state that nothing here tests, so over
#: recurrent state those are refused too.
_SLAB_ONLY = {LATENT: "a latent cache", STATE: "recurrent state"}


def _first(firsts, slot, tok) -> tuple:
    """The tail of every prefill program's outputs: the sampled token,
    and ``firsts`` -- the ``(num_slots,)`` vector of the slots' first
    tokens, which lives on the device -- with it written in.  A decode
    dispatch issued before the host has fetched the token takes it from
    there (``generation.FIRST_ON_DEVICE``)."""
    return tok[0], firsts.at[slot].set(tok[0])


def _default_buckets(max_len: int) -> tuple:
    """Powers of two from 16 up to (and covering) ``max_len``."""
    buckets = []
    b = 16
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return tuple(buckets)


# What differs between the four decode programs once ``_decode_args`` has
# built their arguments, as data ``ServeEngine._decode_step`` reads: the
# builder's name, how many outputs after the KV carry the host fetches,
# how that fetch reads as ``(tokens[iteration, slot, lane],
# count[iteration, slot], iterations)`` — the one shape the walk knows —
# and whether the program returns its final per-slot carry for the next
# dispatch to start from, so that the host may read a dispatch's tokens
# one dispatch late (``ServeEngine._lags`` has the rest of that rule).
# A fused program ran every row of its block (``decode_chunk`` of them); a
# persistent loop reports how many it ran, the drained ring's cursor.  A
# step makes views only, nothing sized ``slots x lanes``: this runs after
# every dispatch with the host's caches cold from the wait, where even one
# ``np.ones`` of 16 cost 19 us against 1.5 us warm (PERF.md, PR 32).


@functools.lru_cache(maxsize=None)
def _ones(shape):
    return np.ones(shape, np.int32)


def _read_block(block):  # one token every iteration: count 1
    return block[:, :, None], _ones(block.shape), len(block)


def _read_ring(ring, valid, cursor):  # count: the valid bit
    return ring[:, :, None], valid, int(cursor)


def _read_spec_blocks(blocks, counts):
    return blocks, counts, len(blocks)


def _read_spec_ring(ring, counts, cursor):
    return ring, counts, int(cursor)


_DECODE_VARIANTS = {  # by (persistent, speculative)
    (False, False): ("_decode_program", 1, _read_block, True),
    # loops on the device until its slots finish: its drain is the sync
    # it exists to make rare, and there is no next dispatch to hide it
    (True, False): ("_persistent_program", 3, _read_ring, False),
    # drafts from ``_hist``, a host mirror copied into every dispatch:
    # the next dispatch needs what the walk of this one writes there
    (False, True): ("_spec_decode_program", 2, _read_spec_blocks, False),
    (True, True): ("_spec_persistent_program", 3, _read_spec_ring, False),
}


# The phases of a tick, ``ServeEngine._phase``'s names: the span, the
# histogram its seconds go to, and the field of the cycle's record
# (``serve.metrics.CycleAccount.KEYS``) its self time is charged to.
# ``serve/decode`` is charged nowhere: its two children cover it, and a
# block's arrival, where one cycle ends and the next begins, lies between
# them.  A prefill's span is scheduling where the host works and a
# first-token wait where it blocks; its call site says which.
_PHASES = {
    name: (f"serve/{name}", f"{name}_s", key)
    for name, key in (
        ("schedule", "schedule"),
        ("prefill", "schedule"),
        ("decode_args", "decode_args"),
        ("decode", None),
        ("dispatch", "dispatch"),
        ("wait", "wait"),
        ("harvest", "harvest"),
    )
}


class _Flight(NamedTuple):
    """A decode dispatch whose outputs the host has not read."""

    outputs: tuple  # the device arrays the row's reader takes
    riders: list  # ``(request, slot)`` as they were when it was dispatched
    iterations: Optional[int]  # a fused scan's; a loop reports its own
    digests: Any  # the numerics observatory's output, or None
    cycle: int  # the dispatch's running number: its spans' ``cycle`` stat


class ServeEngine:
    """Continuous-batching serving engine over a slot-based KV cache.

    Args:
      model: a decoder-only model exposing ``init_cache``,
        ``forward_cached`` and ``forward_decode`` (Llama and GPT-2 ship
        all three).
      num_slots: concurrent request capacity (the decode batch).
      max_len: per-slot cache length; defaults to the model's maximum
        sequence length.  ``prompt + max_new_tokens <= max_len`` is
        enforced at submit.
      eos_token: generation stops when a slot samples this id
        (``finish_reason="stop"``); None decodes to ``max_new_tokens``.
      top_k / top_p: engine-level static sampling filters (baked into the
        compiled programs); per-request ``temperature`` is dynamic, with
        0 = greedy.
      prefill_buckets: padded prompt lengths; each bucket actually used
        compiles one prefill program.  Default: powers of two up to
        ``max_len``.  Explicit buckets are taken AS GIVEN — the largest
        one caps the admissible prompt length (``submit`` raises past
        it); no ``max_len`` bucket is appended behind the caller's back.
      max_tokens_in_flight: admission budget over running requests'
        ``prompt + max_new_tokens`` (default: unbounded).
      decode_chunk: decode steps fused per dispatch (``K``).  Each
        ``step()`` emits up to ``K`` tokens per running slot with ONE
        host sync; requests finishing at in-chunk step ``j`` waste
        ``K - 1 - j`` masked slot-steps and free their slot at the chunk
        boundary.  Raise it when dispatch latency dominates decode (see
        docs/serving.md for choosing K);
        the default 1 is the classic one-sync-per-token step.  Each
        distinct value compiles one decode program.
      decode_mode: ``"chunked"`` (default — the fused K-step scan above,
        the pinned-bit-identical reference) or ``"persistent"`` — one
        ``lax.while_loop`` decode program per ``step()`` that runs to a
        slot-state fixpoint (all slots finished) or a full output ring,
        draining N host syncs per request into ~1 (docs/serving.md).
        ``decode_chunk`` is ignored in persistent mode (the loop bound
        is the ring, not a chunk).
      ring_capacity: persistent mode's device output ring depth (max
        loop iterations per dispatch).  Default ``max_len`` — deep
        enough that any wave of requests finishes inside one loop, so
        drains track generation waves; shrink it to re-open admission
        (and deadline checks) more often at the cost of more drains.
        A request outliving the ring just spans drains.
      persistent_stream: opt in to the ``io_callback`` streamed tail:
        each loop iteration also pushes its ``(tokens, live-mask,
        cursor)`` to the host, giving first-token timestamps before the
        drain lands; the ring drain stays the authoritative token path
        either way.  A
        streaming program is compiled per engine and cached ON the
        engine (its host sink is the engine; an engine-local program is
        collected with it instead of pinning it in the model's shared
        jit store), so sharing a model across streaming engines costs
        one extra compile each.
      page_size: switch the KV cache to the PAGED layout with pages of
        this many tokens (must divide ``max_len``); ``None`` (default)
        keeps the contiguous per-slot slab.  Paged greedy streams are
        bit-identical to the slab engine's.
      num_pages: pool size in paged mode.  Default
        ``num_slots * max_len / page_size + 1`` — the slab engine's
        footprint plus the reserved scratch page, so prefix sharing and
        per-request footprints turn pure win into spare capacity; pass
        less to trade capacity for HBM (admission then gates on free
        pages) or more to keep evicted prefixes around longer.
      prefix_cache: in paged mode, maintain the radix prefix index —
        page-aligned shared prompt prefixes skip straight to page-table
        assignment and prefill computes only the uncached suffix.
        ``False`` keeps paged allocation without sharing.
      params: parameter dict override (e.g. sharded params); default
        ``dict(model.named_parameters())``.
      finished_history: how many finished requests to retain for
        per-request trace export (``dump_trace`` /
        ``finished_requests``).  Each retained request holds its prompt
        array, generated tokens, and lifecycle event list (a handful
        of entries a request, none a tick), so a long-running
        production engine with big prompts may want this small — 0
        disables retention entirely (lifecycle events still accumulate
        on in-flight requests and ride out on ``RequestResult.events``).
      cost_cards: capture a :class:`~torchdistx_tpu.obs.cost.CostCard`
        (XLA cost/memory analysis) for every compiled program at its
        first dispatch, queryable from ``engine.cost_book`` and embedded
        in bench records.  Default True (the engine's program set is
        bounded — one card per prefill bucket family / decode K /
        persistent ring); costs one extra XLA compile per program,
        amortized into warm-up.  ``TDX_COST_CARDS=0`` force-disables.
      hbm_budget: per-device HBM budget in BYTES for the second
        admission gate: before admitting, the engine projects its peak
        footprint (weights + KV cache + the worst per-program temp
        bytes on record — ``memory_plan()``) and refuses admission when
        it exceeds the budget, recording ``("gated", why="hbm_budget")``
        in the request's lifecycle events and bumping the
        ``admissions_rejected_hbm`` counter.  Mutable at runtime
        (raise it and the next ``step()`` re-evaluates); None (default)
        disables the gate — page/token gates alone decide, as before.
      stall_timeout_s: arm a dispatch-stall watchdog
        (:class:`~torchdistx_tpu.obs.watchdog.DispatchWatchdog`) around
        every device dispatch + host sync: a region that overruns this
        many seconds (a hung device or runtime) dumps the flight
        recorder naming the in-flight program and its cost card.  None
        (default) disables.
      mesh: a ``jax.sharding.Mesh`` to serve tensor-parallel over.  The
        params are placed by the declarative ``plan``
        (``parallel.tp.shard_params`` applies its rule projection — a
        no-op for leaves already carrying the target sharding), the
        KV slab/pools are sharded by the plan's ``kv_cache`` rule
        (:func:`_cache_sharding`, default ``P(None, None, tp_axis)`` on
        the stored ``(lead, rows, Hkv * D)`` arrays, with
        ``n_kv_heads % tp`` asserted), page tables stay host-side,
        and every compiled program becomes one SPMD program with
        explicit ``out_shardings`` on its donated KV carry and sampled
        outputs (jit does not propagate input shardings into fresh
        outputs).  Per-layer all-reduce counts/bytes are recorded
        analytically into any active ``obs.comm.comm_audit`` — GSPMD
        collectives are invisible to Python-level tracing, so the engine
        pins the Megatron closed form (2 per block) at dispatch time,
        exactly like the training TP leg.  ``memory_plan()`` accounts
        per-shard bytes, so the HBM admission gate sees the ``1/tp``
        footprint that makes 7B+ models servable.  None (default): the
        single-device/replicated engine, unchanged.
      plan: the :class:`~torchdistx_tpu.parallel.plan.ShardingPlan`
        that drives the mesh path — parameter placement AND the KV-pool
        layout come from the one declarative object (the same plan a
        ``Trainer`` / ``reshard_to_plan`` / fleet ``handoff_to`` would
        hold).  Default when ``mesh`` is given:
        ``parallel.tp.llama_tp_plan(mesh, tp_axis)``.
      tp_axis: the mesh axis name to tensor-shard over (default
        ``"tp"``); other axes of the mesh are left replicated.
      chunked_prefill: prefill-chunk threshold in tokens.  A prompt (or
        paged uncached suffix) LONGER than this is prefilled in chunks
        of at most this many tokens — each chunk a warm (traced
        ``cache_pos``) dispatch — with one decode dispatch interleaved
        between consecutive chunks, so a long prompt no longer stalls
        every active decode slot for its whole prefill (the tail-latency
        half of the serving win; the ``tpot_s``/inter-token-gap effect
        is measured by ``bench_serve.py --chunked-prefill``).  Must be
        one of ``prefill_buckets`` (each full chunk reuses that bucket's
        program).  Token streams are unchanged — chunking only
        reschedules the prefill compute.  None (default) disables.
      speculate: draft this many candidate tokens per slot per decode
        iteration by SELF-speculation (prompt-lookup / n-gram drafting
        against the slot's own token history — no second model), verify
        all ``speculate + 1`` positions in ONE batched model call, and
        accept the longest matching prefix greedily — entirely inside
        the compiled decode body (``generation._make_spec_decode_body``),
        so the persistent loop's sync discipline is untouched:
        ``host_syncs`` still equals ring drains, each drain just carries
        up to ``speculate + 1`` tokens per slot per iteration.  Greedy
        streams stay bit-identical to ``speculate=0`` (row 0 of the
        verify block IS the one-token forward; accepted rows match the
        greedy argmax by construction); sampled slots (temperature > 0)
        keep their exact key schedule by forcing accept length 0.  The
        default 0 disables — the engine compiles the classic one-token
        programs, byte-for-byte the pre-speculation dispatch.  See
        docs/serving.md for choosing K.
      spec_ngram: trailing-token match length for the draft lookup
        (default 2).  Longer n-grams draft more conservatively (fewer,
        better-grounded matches); 1 is aggressive last-token matching.
    """

    def __init__(
        self,
        model: Any,
        *,
        num_slots: int = 4,
        max_len: Optional[int] = None,
        eos_token: Optional[int] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        prefill_buckets: Optional[Sequence[int]] = None,
        max_tokens_in_flight: Optional[int] = None,
        decode_chunk: int = 1,
        decode_mode: str = "chunked",
        ring_capacity: Optional[int] = None,
        persistent_stream: bool = False,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        kv_dtype: Optional[str] = None,
        prefix_cache: bool = True,
        params: Optional[dict] = None,
        finished_history: int = 1024,
        cost_cards: bool = True,
        numerics: Optional[bool] = None,
        hbm_budget: Optional[int] = None,
        stall_timeout_s: Optional[float] = None,
        mesh: Optional[Any] = None,
        plan: Optional[Any] = None,
        tp_axis: str = "tp",
        chunked_prefill: Optional[int] = None,
        speculate: int = 0,
        spec_ngram: int = 2,
        record: Any = None,
    ):
        _check_sampling_args(top_k, top_p)
        cfg = getattr(model, "cfg", None)
        limit = getattr(cfg, "max_seq_len", None) or getattr(
            cfg, "n_positions", None
        )
        if max_len is None:
            max_len = limit
        if max_len is None:
            raise ValueError(
                "max_len is required for models without a sequence limit"
            )
        if limit is not None and max_len > limit:
            raise ValueError(
                f"max_len {max_len} exceeds the model's maximum sequence "
                f"length {limit}"
            )
        self.model = model
        # what the model's cache entries hold, layer by layer
        # (kv_cache.entry_kind).  A latent entry (multi-head latent
        # attention, models/deepseek_v3.py) and a recurrent state (a
        # state-space layer, models/jamba.py) are served from the slab
        # with the default programs only; everything else is refused
        # here, by name, not half-built (_SLAB_ONLY has the reasons)
        kinds = cache_kinds(model)
        self.latent = LATENT in kinds
        self.recurrent = STATE in kinds
        rec = resolve_record(record)
        for kind in (k for k in _SLAB_ONLY if k in kinds):
            refused = {
                "page_size (a paged cache, and with it the prefix cache)":
                    page_size is not None or num_pages is not None,
                "kv_dtype='int8'":
                    canonicalize_kv_dtype(kv_dtype) == "int8",
                "speculate": bool(speculate),
                "decode_mode='persistent'": decode_mode == "persistent",
                "chunked_prefill": chunked_prefill is not None,
                "mesh (tensor parallelism)": mesh is not None,
            }
            if kind == STATE:
                refused["record (the session recorder)"] = bool(
                    getattr(rec, "enabled", False)
                )
            bad = [name for name, asked in refused.items() if asked]
            if bad:
                raise ValueError(
                    f"{', '.join(bad)}: not supported over {_SLAB_ONLY[kind]} "
                    f"({type(model).__name__}): it is served from the "
                    "slab cache with the chunked decode program only"
                )
        self.params = (
            params if params is not None else dict(model.named_parameters())
        )
        # -- mesh path: TP-shard params + cache, SPMD-compile programs --
        self.mesh = mesh
        self.tp_axis = str(tp_axis)
        if mesh is not None:
            if self.tp_axis not in mesh.axis_names:
                raise ValueError(
                    f"mesh has no '{self.tp_axis}' axis (axes: "
                    f"{tuple(mesh.axis_names)}) — pass tp_axis="
                )
            self.tp = int(mesh.shape[self.tp_axis])
            from ..parallel.tp import llama_tp_plan, shard_params

            if plan is None:
                plan = llama_tp_plan(mesh, self.tp_axis)
            if plan.mesh is not mesh and tuple(
                plan.mesh.devices.flat
            ) != tuple(mesh.devices.flat):
                raise ValueError(
                    "plan.mesh does not cover the engine mesh — build "
                    "the plan on the serving mesh (plan.with_mesh)"
                )
            self.params = shard_params(self.params, plan.as_rule())
        else:
            if plan is not None:
                raise ValueError("plan requires mesh=")
            self.tp = 1
        self.plan = plan
        # closed-form comm accounting needs the block geometry; a model
        # whose config doesn't expose it serves fine, just unaudited
        _layers = getattr(cfg, "n_layers", None) or getattr(
            cfg, "n_layer", None
        )
        _dim = getattr(cfg, "dim", None) or getattr(cfg, "n_embd", None)
        self._tp_geom = (
            (int(_layers), int(_dim)) if _layers and _dim else None
        )
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.eos_token = eos_token
        self.top_k = top_k
        self.top_p = top_p
        if decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, got {decode_chunk}")
        self.decode_chunk = int(decode_chunk)
        if decode_mode not in ("chunked", "persistent"):
            raise ValueError(
                f"decode_mode must be 'chunked' or 'persistent', got "
                f"{decode_mode!r}"
            )
        self.decode_mode = decode_mode
        self._persistent = decode_mode == "persistent"
        if self._persistent:
            if ring_capacity is None:
                ring_capacity = self.max_len
            if ring_capacity < 1:
                raise ValueError(
                    f"ring_capacity must be >= 1, got {ring_capacity}"
                )
            self.ring_capacity: Optional[int] = int(ring_capacity)
        else:
            if ring_capacity is not None:
                raise ValueError(
                    "ring_capacity requires decode_mode='persistent'"
                )
            if persistent_stream:
                raise ValueError(
                    "persistent_stream requires decode_mode='persistent'"
                )
            self.ring_capacity = None
        if speculate < 0:
            raise ValueError(f"speculate must be >= 0, got {speculate}")
        self.speculate = int(speculate)
        self.spec_ngram = int(spec_ngram)
        if self.speculate:
            if self.spec_ngram < 1:
                raise ValueError(
                    f"spec_ngram must be >= 1, got {spec_ngram}"
                )
            if persistent_stream:
                raise ValueError(
                    "speculate is not supported with persistent_stream: "
                    "the streamed tail pushes one token per iteration, "
                    "but a speculative iteration emits a variable-length "
                    "block only the drain walk can consume"
                )
        if prefill_buckets is None:
            buckets = _default_buckets(self.max_len)
        else:
            buckets = tuple(sorted(int(b) for b in prefill_buckets))
            if not buckets or buckets[0] < 1:
                raise ValueError(f"invalid prefill_buckets {prefill_buckets}")
            if buckets[-1] > self.max_len:
                raise ValueError(
                    f"bucket {buckets[-1]} exceeds max_len {self.max_len}"
                )
            # explicit buckets are respected as given: the largest one is
            # the prompt-length ceiling submit() enforces.  (Silently
            # appending a max_len bucket used to hide that ceiling AND
            # compile a program the caller never asked for.)
        self.prefill_buckets = buckets
        if chunked_prefill is not None:
            chunked_prefill = int(chunked_prefill)
            if chunked_prefill not in self.prefill_buckets:
                raise ValueError(
                    f"chunked_prefill ({chunked_prefill}) must be one of "
                    f"prefill_buckets {self.prefill_buckets} — every full "
                    "chunk is dispatched through that bucket's program"
                )
        self.chunked_prefill = chunked_prefill
        # KV cache placement: head-axis sharded on the mesh path,
        # replicated-over-params'-mesh / default-device otherwise
        _kv_heads = getattr(cfg, "n_kv_heads", None) or getattr(
            cfg, "n_heads", None
        ) or getattr(cfg, "n_head", None)
        _placement = _cache_sharding(
            self.params,
            mesh=mesh,
            tp_axis=self.tp_axis,
            kv_heads=None if _kv_heads is None else int(_kv_heads),
            plan=self.plan,
        )
        from jax.sharding import NamedSharding, PartitionSpec

        # explicit out_shardings for every compiled program's outputs
        # (the donated KV carry and the sampled token/ring outputs —
        # jit does not propagate input shardings into fresh outputs);
        # None on the single-device path, where committed inputs pin
        # the outputs already
        self._kv_sharding = (
            _placement if isinstance(_placement, NamedSharding) else None
        )
        self._repl_sharding = (
            None
            if self._kv_sharding is None
            else NamedSharding(self._kv_sharding.mesh, PartitionSpec())
        )
        # int8 KV quantization (kv_dtype="int8"): the caches store
        # per-layer (k, v, k_scale, v_scale) 4-tuples and every program
        # quantizes on write / dequantizes on read (serve/kv_cache.py);
        # "bfloat16"/"float16"/"float32" are plain cast caches (A/B
        # baselines); None keeps the model's own cache dtype
        self.kv_dtype = canonicalize_kv_dtype(kv_dtype)
        self._prefix_cache_flag = bool(prefix_cache)
        self.page_size = None if page_size is None else int(page_size)
        self.paged = self.page_size is not None
        if self.paged:
            if num_pages is None:
                # slab-equivalent HBM + the reserved scratch page
                num_pages = (
                    self.num_slots * (self.max_len // self.page_size) + 1
                )
            self.num_pages = int(num_pages)
            self.pool = PagePool(self.num_pages)
            self.prefix_index = (
                RadixPrefixIndex(self.page_size) if prefix_cache else None
            )
            self.cache: Any = PagedKVCache(
                model,
                self.num_slots,
                self.max_len,
                self.page_size,
                self.num_pages,
                placement=_placement,
                kv_dtype=self.kv_dtype,
            )
        else:
            if num_pages is not None:
                raise ValueError("num_pages requires page_size")
            self.num_pages = None
            self.pool = None
            self.prefix_index = None
            self.cache = SlotKVCache(
                model,
                self.num_slots,
                self.max_len,
                placement=_placement,
                kv_dtype=self.kv_dtype,
            )
        self.kv_quantized = self.cache.quantized
        # numerics observatory (ISSUE 19): digests fuse into the serve
        # programs at trace time and ride each dispatch as one extra
        # output — harvested ONLY at the dispatch's existing sync, so
        # host_syncs/decode_dispatches are exactly unchanged either way
        self.numerics = (
            numerics_enabled() if numerics is None else bool(numerics)
        )
        self.numerics_book = NumericsBook()
        # an expert model's rows and groups (nn.moe.moe_count_tape): one
        # more output of the slab prefill and chunked decode programs,
        # accumulated on the device by ServeMetrics.add_device_counts
        self._moe_counts = (
            bool(getattr(model, "moe_counters", False))
            and not self.numerics
            and mesh is None
        )
        self._pending_digests: list = []
        self._kv_quant_alarmed = False
        # the dtype actually stored (model default resolved), for the
        # attributable refusal/plan naming satellite
        self.kv_dtype_name = self.cache.kv_dtype_name
        self.scheduler = Scheduler(self.num_slots, max_tokens_in_flight)
        self.metrics = self._new_metrics(
            0.0 if self.kv_quantized else None,
            0.0 if self.kv_quantized else None,
        )
        self._sampler = _make_slot_sampler(jnp.int32, top_k, top_p)
        # a prefill's first token parks here (slot -> 0-d device array,
        # in admission order) where its fetch is deferred: to the next
        # ring drain's single sync in persistent mode, to the end of the
        # step that admitted on a row that lags (``_first_tokens``), which
        # also keeps the dispatch's host seconds until then
        self._pending_first: dict = {}
        self._prefill_host_s: dict = {}
        # THE LAG.  Whether this engine reads a decode dispatch's tokens
        # one dispatch late: the row of ``_DECODE_VARIANTS`` has to return
        # its carry, and prefills must be whole -- a chunked prefill runs
        # decode dispatches INSIDE an admission (``_interleave_decode``)
        # around a slot parked by the host, each of which would have to
        # be read before the next chunk's bookkeeping; those engines
        # settle at once.  Nothing else decides it: no option, no variable
        self._lags = (
            _DECODE_VARIANTS[self._persistent, bool(self.speculate)][3]
            and self.chunked_prefill is None
        )
        self._in_flight: Optional[_Flight] = None
        # the decode dispatches issued, a running number over the engine's
        # life (``reset_metrics`` does not restart it): the n-th execution
        # of the decode program on the device is ``cycle`` n of the spans
        # and of the metrics' cycle records
        self._cycle = 0
        # a small output of the last program queued (its tokens): ready
        # means the device's queue is empty.  ``_nothing_queued`` while
        # the host knows that without asking: before the first dispatch
        # and after a ``_settle()``, where there was nothing to overlap
        self._last_out: Any = None
        self._nothing_queued = True
        # what the fused one-token program starts a slot from
        # (generation.KEEP_CARRY / FROM_HOST / FIRST_ON_DEVICE): the host
        # marks the slots it changed since the last dispatch
        self._source = np.zeros(self.num_slots, np.int32)
        # the last dispatch's final carry and the vector the prefill
        # programs write their sampled tokens into: device arrays that
        # only ever travel from one program to the next
        where = self._repl_sharding
        if where is None:
            where = jax.tree_util.tree_leaves(self.cache.kv)[0].sharding
        self._carry = jax.device_put(
            np.zeros((SLOT_STATE_ROWS, self.num_slots), np.int32), where
        )
        self._firsts = jax.device_put(
            np.zeros(self.num_slots, np.int32), where
        )
        # streamed-tail host sink: (monotonic_ts, tokens, live, cursor)
        # per loop iteration, consumed (and cleared) at each drain
        self._stream_events: list = []
        self._stream_cb = None
        self._stream_program = None  # engine-local jit (see _persistent_program)
        self.stream_supported: Optional[str] = None
        if persistent_stream and self._persistent:
            self._stream_cb = self._build_stream_cb()
        self._last_tok = np.zeros(self.num_slots, np.int32)
        self._temps = np.zeros(self.num_slots, np.float32)
        self._seeds = np.zeros(self.num_slots, np.int32)
        self._ntok = np.zeros(self.num_slots, np.int32)  # tokens sampled
        self._budget = np.zeros(self.num_slots, np.int32)  # max_new_tokens
        # speculative drafting history: the host mirror of each slot's
        # full token stream (prompt + everything generated), shipped as
        # a tiny int32 dynamic input to every spec decode dispatch — the
        # device's n-gram draft lookup reads it, and the loop body keeps
        # its on-device copy current across iterations within a dispatch
        self._hist = np.zeros((self.num_slots, self.max_len), np.int32)
        # bounded history of finished requests, kept for per-request
        # trace export (dump_trace) — each carries its full lifecycle
        # event list and the timestamps the aggregate histograms used.
        # maxlen=0 (finished_history=0) retains nothing.
        self._finished: deque = deque(maxlen=int(finished_history))
        # cost observatory: one CostCard per compiled program, captured
        # at first dispatch (obs.cost).  Engine-owned book — two engines
        # on one model never collide
        self.cost_book = CostBook()
        self._cards_on = bool(cost_cards) and not _cost_force_disabled()
        self._carded: set = set()
        # live HBM capacity gate (obs.memory.capacity_plan); mutable.
        # the static plan components (weights, kv) are computed once on
        # first use — the gate re-reads only the cost book's temps
        self.hbm_budget = hbm_budget
        self._static_footprint: Optional[dict] = None
        self._gate = self._make_admission_gate()
        # elastic drain state (drain()/migrate_to()): a draining engine
        # refuses new submissions and admits nothing, but keeps stepping
        # its running slots
        self._draining = False
        # dispatch-stall watchdog (obs.watchdog)
        self.watchdog = None
        if stall_timeout_s is not None:
            from ..obs.watchdog import DispatchWatchdog

            self.watchdog = DispatchWatchdog(
                stall_timeout_s, book=self.cost_book
            )
        # session black box (ISSUE 20): the recorder streams geometry +
        # driver events and folds a digest chain at every drain boundary
        # (obs/blackbox.py).  Under TDX_SESSION_RECORD=0 resolve_record
        # yields a disabled recorder and every hook below is dead.
        self.recorder = None
        self._bb_on = False
        self._bb_driver = True
        self._bb_source = "engine"
        self._bb_in_drain = False
        self._bb_finished_pending: list = []
        if rec is not None:
            self.attach_recorder(rec)

    # -- public API ------------------------------------------------------

    def submit(
        self,
        prompt,
        *,
        max_new_tokens: int,
        temperature: float = 0.0,
        seed: int = 0,
        deadline_s: Optional[float] = None,
        trace_id: Optional[int] = None,
    ) -> RequestHandle:
        """Enqueue one request; returns immediately.  ``step()`` (or
        ``run``) drives it to completion.  ``trace_id`` propagates an
        existing fleet-scoped trace context (an external router's, say);
        left None, the scheduler mints a process-unique one — either way
        the id rides the request through ``handoff_to``/``migrate_to``
        so a cross-replica trace merge keys on it, not on the
        per-scheduler (colliding) rid."""
        if self._draining:
            # named refusal, not a silent queue-forever: a draining
            # engine will never admit again, so accepting the submit
            # would strand the request
            self.metrics.count("submits_rejected_draining")
            raise RuntimeError(
                "engine is draining: new submissions are refused — "
                "submit to the migration target engine instead"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the slot cache length "
                f"{self.max_len} — the prompt may be at most "
                f"{self.max_len - max_new_tokens} tokens for this budget"
            )
        if prompt.size > self.prefill_buckets[-1]:
            # fail HERE, not inside the prefill jit: with explicit
            # prefill_buckets the largest bucket is the longest prompt the
            # compiled prefill programs can take
            raise ValueError(
                f"prompt ({prompt.size}) exceeds the largest prefill "
                f"bucket ({self.prefill_buckets[-1]}) — pass a larger "
                "bucket in prefill_buckets (up to max_len "
                f"{self.max_len}) or shorten the prompt"
            )
        if self.paged:
            need = -(-(prompt.size + max_new_tokens) // self.page_size)
            if need > self.pool.capacity:
                # no admission order can ever free enough pages; fail at
                # submit with the limit named, like the bucket check above
                raise ValueError(
                    f"prompt ({prompt.size}) + max_new_tokens "
                    f"({max_new_tokens}) needs {need} pages of "
                    f"{self.page_size} tokens, but the "
                    f"{self.kv_dtype_name} cache pool holds only "
                    f"{self.pool.capacity} allocatable pages — raise "
                    "num_pages or shrink the request"
                )
        req = Request(
            rid=-1,
            prompt=prompt,
            max_new_tokens=int(max_new_tokens),
            temperature=float(temperature),
            # the sampler keys on an int32 seed; mask wide (time/hash)
            # seeds here rather than overflowing mid-step after the slot
            # is already assigned
            seed=int(seed) & 0x7FFFFFFF,
            deadline_s=deadline_s,
            trace_id=None if trace_id is None else int(trace_id),
        )
        self.scheduler.submit(req)
        self.metrics.count("requests_submitted")
        if self._bb_on:
            if self._bb_driver:
                self.recorder.record_submit(self._bb_source, req)
            else:
                # fleet-driven replica: the fleet recorded the submit;
                # register identity so drain tokens key on the session id
                self.recorder.register_request(req.trace_id)
        return RequestHandle(req)

    def step(self) -> int:
        """One scheduler tick: expire deadlines, admit new requests into
        free slots (one prefill dispatch each), then run ONE fused decode
        dispatch — ``decode_chunk`` on-device steps — over every slot.
        Admission therefore lands exactly at chunk boundaries, and
        running-request deadlines are checked once per chunk.  Returns
        the number of unfinished requests (queued + running).

        **When a step's tokens become visible.**  On the fused one-token
        program with whole prefills (``_lags``: the default engine, slab
        or paged) a decode dispatch's tokens are read one dispatch late:
        this step issues its dispatch ``D(k)`` behind the previous
        step's ``D(k-1)``, which is still running, and only then fetches
        and walks ``D(k-1)``'s block, so the device always has the next
        dispatch queued while the host works.  The tokens of the dispatch
        a ``step()`` issues are in ``Request.generated`` at the end of
        the FOLLOWING ``step()``; a request's first token at the end of
        the step that admitted it; a finish one step after the device
        froze the slot (the slot rides that one dispatch frozen,
        ``lagged_slot_steps``), so a running request's deadline can
        overshoot by two chunks' wall time, not one.  Whoever needs the
        host mirrors and the cache to agree with the device — ``drain``,
        ``migrate_to``, ``handoff_to``, the end of ``run`` — calls
        ``_settle()`` first; ``finished_requests()`` and the metrics
        report what the host has seen.  Every other engine (persistent,
        speculative, ``chunked_prefill``) reads each dispatch at once:
        the same step, settled right after its dispatch."""
        with self._phase("schedule"):
            self._schedule("step")
            pause = not self.scheduler.running or self._in_flight_ends_all()
            if pause and self._in_flight is None:
                self._observe_gauges()  # nothing to decode: the tick ends
        if pause:
            # nothing to issue: whatever is in flight is read now, so that
            # an idle engine has nothing queued when the next request comes
            self._settle()
        else:
            # ends in ``serve/harvest``, which observes the gauges
            self._decode_step()
        return self.scheduler.queue_depth + len(self.scheduler.running)

    def _in_flight_ends_all(self) -> bool:
        """Whether the host can prove, from its own counts, that the
        dispatch in flight is the last one every running request needs:
        each rode it and exhausts its budget inside it (an EOS cannot be
        foreseen; a request admitted since has not ridden it).  Another
        dispatch would only carry frozen slots."""
        flight = self._in_flight
        if flight is None:
            return False
        running = self.scheduler.running
        if any(
            self._ntok[req.slot] + flight.iterations < self._budget[req.slot]
            for req in running
        ):
            return False  # in steady state the first request says so
        riders = {id(req) for req, _ in flight.riders}
        return all(id(req) in riders for req in running)

    def _phase(self, name: str, sink=None, key=None, **stats):
        """One phase of a tick: the span ``serve/<name>`` (in any
        profile, and on the host tracer when enabled; ``stats`` as the
        annotation's stats, ``cycle=n``) with its host seconds recorded
        into the ``<name>_s`` histogram, or handed to ``sink`` where the
        record is made later, and its self time charged to the running
        cycle's ``key`` (``_PHASES`` has each phase's own)."""
        span, hist, own_key = _PHASES[name]
        metrics = self.metrics
        sink = sink or getattr(metrics, hist).record
        key = key or own_key
        if key is not None:
            sink = metrics.cycles.entered(key, sink)
        return timed_annotation(span, sink, **stats)

    def _device_idle(self) -> bool:
        """Whether the last program queued has ended: every program
        donates the cache and returns it, so with that one's outputs
        ready the device's queue is empty."""
        return self._last_out.is_ready()

    def _count_if_starved(self, kind: str) -> None:
        """Immediately before a ``prefill`` or ``decode`` program is
        called: a dispatch that finds the chip idle is counted, but for
        the first after a ``_settle()`` or on a fresh engine."""
        if self._nothing_queued:
            self._nothing_queued = False
        elif self._device_idle():
            self.metrics.cycles.starved[kind] += 1

    def _schedule(self, tick_kind: str) -> None:
        """The ``serve/schedule`` phase of a tick: expire deadlines, then
        admit into free slots, one prefill each (its dispatch and
        first-token sync are the child span ``serve/prefill``)."""
        if self._bb_on and self._bb_driver and not self._bb_in_drain:
            self.recorder.tick += 1
            self.recorder.record(tick_kind, tick=self.recorder.tick)
        now = time.monotonic()
        for req in self.scheduler.expire_queued(now):
            self._count_finish(req)
        for req in list(self.scheduler.running):
            if req.expired(now):
                self._finish(req, "deadline", now)
        gate = (
            self._gate
            if (self._draining or self.paged or self.hbm_budget is not None)
            else None
        )
        for req, slot in self.scheduler.admit(now, gate=gate):
            self._prefill_request(req, slot)

    def _observe_gauges(self) -> None:
        """Queue depth, slot occupancy and pages in use, sampled where a
        decode dispatch's harvest ends (or the tick, if it ran none)."""
        self.metrics.observe_gauges(
            self.scheduler.queue_depth, self.cache.active_count
        )
        if self.paged:
            self.metrics.observe_pages(self.pool.in_use)

    def step_prefill(self) -> int:
        """The disaggregated prefill role's scheduler tick (docs/
        serving.md, Fleet): expire deadlines and admit + prefill into
        free slots exactly like :meth:`step`, but NEVER run a decode
        dispatch — a prefilled request parks in its slot (first token
        already sampled and recorded) until ``handoff_to`` moves its KV
        to a decode engine.  Chunked mode only: the persistent loop's
        deferred first-token fetch would ride a decode drain this role
        never performs.  Returns unfinished requests (queued + parked).
        """
        if self._persistent:
            raise RuntimeError(
                "step_prefill requires decode_mode='chunked' — the "
                "persistent loop defers first-token fetches to a decode "
                "drain a prefill-role engine never runs"
            )
        with self._phase("schedule"):
            self._schedule("step_prefill")
            self._observe_gauges()
        self._settle()  # the first tokens, where their fetch was deferred
        return self.scheduler.queue_depth + len(self.scheduler.running)

    def run(
        self, requests: Iterable[Union[dict, Any]], *, max_new_tokens: int = 32
    ) -> List[RequestResult]:
        """Batch-offline mode: submit everything, step until drained,
        return results in submission order.  Each request is either a
        ``submit`` kwargs dict (``{"prompt": ..., "max_new_tokens": ...}``)
        or a bare token sequence (decoded with ``max_new_tokens``)."""
        handles = []
        for r in requests:
            if isinstance(r, dict):
                handles.append(self.submit(**r))
            else:
                handles.append(self.submit(r, max_new_tokens=max_new_tokens))
        while self.step():
            pass
        self._settle()  # an EOS finish leaves its successor in flight
        return [h.result() for h in handles]

    # -- session black box (obs/blackbox.py) -----------------------------

    def attach_recorder(
        self,
        recorder,
        *,
        source: str = "engine",
        driver: bool = True,
        geometry_extra: Optional[dict] = None,
    ) -> None:
        """Wire a :class:`~torchdistx_tpu.obs.blackbox.SessionRecorder`
        into this engine.  ``driver=True`` (standalone engine): submits
        and steps are recorded as driver events.  ``driver=False``
        (fleet replica): the fleet owns the driver log and this engine
        contributes only its geometry and its drain digest folds, under
        ``source`` (the replica name)."""
        self.recorder = recorder
        self._bb_source = str(source)
        self._bb_driver = bool(driver)
        self._bb_on = bool(getattr(recorder, "enabled", False))
        self._bb_finished_pending = []
        if not self._bb_on:
            return
        recorder.record(
            "geometry",
            source=self._bb_source,
            **self.session_geometry(),
            **(geometry_extra or {}),
        )
        if recorder.path:
            # every flight/crash/watchdog dump names the black box it
            # pairs with — an incident artifact that cannot be replayed
            # is a post-mortem, not a reproduction
            try:
                from ..obs.flight import get_flight_recorder

                get_flight_recorder().session_path = recorder.path
            except Exception:
                pass

    def session_geometry(self) -> dict:
        """Everything :func:`~torchdistx_tpu.obs.blackbox.replay_session`
        needs to rebuild this engine, plus attribution (plan
        fingerprint, resolved storage dtype, model class)."""
        return {
            "model": type(self.model).__name__,
            "num_slots": self.num_slots,
            "max_len": self.max_len,
            "eos_token": self.eos_token,
            "top_k": self.top_k,
            "top_p": self.top_p,
            "prefill_buckets": list(self.prefill_buckets),
            "decode_chunk": self.decode_chunk,
            "decode_mode": self.decode_mode,
            "ring_capacity": self.ring_capacity,
            "page_size": self.page_size,
            "num_pages": self.num_pages,
            "kv_dtype": self.kv_dtype,
            "kv_dtype_name": self.kv_dtype_name,
            "chunked_prefill": self.chunked_prefill,
            "speculate": self.speculate,
            "spec_ngram": self.spec_ngram,
            "prefix_cache": self._prefix_cache_flag,
            "tp": self.tp,
            "plan": (
                None
                if self.plan is None
                else getattr(self.plan, "name", type(self.plan).__name__)
            ),
        }

    def _record_drain(self) -> None:
        """Fold one drain boundary into the session digest chain: the
        integer-counter delta plus every token this drain's walk
        appended, keyed by session request id.  Sits at the END of each
        walk that counted ``host_syncs``, reading only state the sync
        already materialized — recording adds ZERO host syncs (pinned
        in tests/test_blackbox.py and the serve expectations)."""
        if not self._bb_on:
            return
        rec = self.recorder
        toks: dict = {}
        pend, self._bb_finished_pending = self._bb_finished_pending, []
        for req in list(self.scheduler.running) + pend:
            sid = rec.session_rid(req.trace_id)
            if sid is None:
                continue  # submitted before the recorder attached
            done = getattr(req, "_bb_emitted", 0)
            tail = req.generated[done:]
            if tail:
                toks[sid] = [int(t) for t in tail]
                req._bb_emitted = len(req.generated)
        rec.drain(self._bb_source, self.metrics.counters, toks)

    # -- elastic drain / live migration ----------------------------------

    def drain(self, *, complete: bool = False) -> int:
        """Stop admission so the engine can be resized or retired.

        Queued requests stay queued — the FCFS head gets a
        ``("gated", {"why": "draining"})`` lifecycle event naming why it
        stopped moving — and new :meth:`submit` calls raise.  Running
        slots keep their KV state; with ``complete=True`` the engine
        steps until every running request finishes (queued ones still
        wait for :meth:`migrate_to`), otherwise they stay suspended at
        the current chunk boundary with positions, host sampling state,
        and cache rows intact.  Persistent-mode pending first tokens are
        flushed (one host sync) so the suspended state is complete.
        Returns the number of unfinished requests (queued + suspended).
        """
        if self._bb_on and self._bb_driver and not self._bb_in_drain:
            # intent log, recorded BEFORE execution: a kill mid-drain
            # leaves the event, and replay re-enters the same drain.
            # Inner step()s are the drain's own, not driver events.
            self.recorder.record("engine_drain", complete=bool(complete))
        self._bb_in_drain = True
        try:
            return self._drain_impl(complete=complete)
        finally:
            self._bb_in_drain = False

    def _drain_impl(self, *, complete: bool) -> int:
        self._draining = True
        self._settle()  # the suspended state is what the device holds
        now = time.monotonic()
        # the queued head learns WHY it stopped moving right away — not
        # at some later step(), and regardless of whether a slot is free
        # (Scheduler.admit only consults the gate when one is)
        if self.scheduler.queue_depth:
            Scheduler._record_gated(
                self.scheduler.queued[0], now, "draining"
            )
        by_slot = {r.slot: r for r in self.scheduler.running}
        for slot, pending in list(self._pending_first.items()):
            del self._pending_first[slot]
            req = by_slot.get(slot)
            if req is None:
                continue
            tok = int(np.asarray(pending))
            self.metrics.count("host_syncs")
            self._harvest_numerics()
            self._record_first(req, tok, now)
            self._check_finished(req, tok, now)
        self._record_drain()  # the flush above was a drain boundary
        if complete:
            while self.scheduler.running:
                self.step()
        return self.scheduler.queue_depth + len(self.scheduler.running)

    def migrate_to(self, target: "ServeEngine") -> dict:
        """Hand every unfinished request to ``target`` — a differently
        shaped engine (other TP degree, other slot count) over the same
        model — without dropping any of them.

        Suspended running slots move WITH their KV state: slab rows (or
        page chains) are gathered out of this engine's sharded cache and
        scattered into the target's, host sampling state rides along,
        and each request resumes mid-stream — a greedy stream completes
        bit-identically to an undrained run.  Queued requests transfer
        rid-intact, so every outstanding :class:`RequestHandle` stays
        valid against the target.  Validation happens before any state
        moves (a failed migration leaves both engines untouched).

        Every KV redistribution is booked into the active comm audit as
        its closed-form ring all-gather (``parallel/reshard.py`` model:
        group ``g`` from the split-count gcd, wire = ``S*(g-1)/g``);
        same-layout moves book nothing.  Returns a summary dict with
        the migrated counts, total ``wire_bytes``, and both shapes.
        """
        if target is self:
            raise ValueError("cannot migrate an engine into itself")
        self._refuse_state_move("migrate_to", target)
        self._settle()  # a drained engine may have been stepped since
        if target._draining:
            raise RuntimeError(
                "migration target is itself draining — migrate to a "
                "live engine"
            )
        if not self._draining:
            self.drain()
        now = time.monotonic()
        running = sorted(
            self.scheduler.running,
            key=lambda r: (r.admitted_at or 0.0, r.rid),
        )
        queued = self.scheduler.queued
        # -- validate everything before moving anything ------------------
        if self.paged != target.paged:
            raise RuntimeError(
                "cannot migrate between slab and paged engines — KV "
                "layouts are not interconvertible in place"
            )
        if self.max_len != target.max_len:
            raise RuntimeError(
                f"KV geometry mismatch: source max_len {self.max_len} "
                f"!= target max_len {target.max_len}"
            )
        if self.paged and self.page_size != target.page_size:
            raise RuntimeError(
                f"page-size mismatch: source {self.page_size} != "
                f"target {target.page_size}"
            )
        if self.kv_dtype_name != target.kv_dtype_name:
            # a requantization pass could bridge this, but silently
            # changing a stream's cache precision mid-flight would break
            # the bit-stability contract the move advertises
            raise RuntimeError(
                f"KV dtype mismatch: source {self.kv_dtype_name} cache "
                f"!= target {target.kv_dtype_name} — KV moves never "
                "requantize"
            )
        free_b = target.scheduler.free_slot_count
        if len(running) > free_b:
            raise RuntimeError(
                f"{len(running)} suspended request(s) need slots but the "
                f"target has only {free_b} free — drain(complete=True) "
                "further, or migrate to a larger engine"
            )
        if self.paged:
            need = sum(len(r.pages or ()) for r in running)
            if need > target.pool.free_count:
                raise RuntimeError(
                    f"suspended requests hold {need} KV page(s) but the "
                    f"target pool has only {target.pool.free_count} free"
                )
        for q in queued:
            if q.prompt.size > target.prefill_buckets[-1]:
                raise RuntimeError(
                    f"queued request {q.rid}: prompt ({q.prompt.size}) "
                    "exceeds the target's largest prefill bucket "
                    f"({target.prefill_buckets[-1]})"
                )
            if target.paged:
                need = -(-(q.cost) // target.page_size)
                if need > target.pool.capacity:
                    raise RuntimeError(
                        f"queued request {q.rid} needs {need} pages but "
                        f"the target pool holds only "
                        f"{target.pool.capacity}"
                    )
        # -- move suspended slots (KV + host sampling state) -------------
        wire = 0
        n_coll = 0
        pages_moved = 0
        for req in running:
            s_a, s_b, w, c, moved = self._move_running(target, req)
            wire += w
            n_coll += c
            pages_moved += moved
            req.record_event("migrated", ts=now, from_slot=s_a, to_slot=s_b)
            self.metrics.count("requests_migrated_out")
            target.metrics.count("requests_migrated_in")
        # -- move the queue (rid-intact, FCFS order preserved) -----------
        for req in self.scheduler.drain_queue():
            req.record_event("migrated", ts=now, queued=True)
            target.scheduler.adopt_queued(req)
            self.metrics.count("requests_migrated_out")
            target.metrics.count("requests_migrated_in")
        if self.paged and self.prefix_index is not None:
            # the source cache is decommissioned: shared-prefix pages the
            # radix index kept pinned for future hits have nothing left
            # to hit against — release them all
            self.prefix_index.evict(self.pool, self.pool.capacity)
        self.metrics.count("migration_wire_bytes", wire)
        return {
            "migrated_running": len(running),
            "migrated_queued": len(queued),
            "pages_moved": pages_moved,
            "wire_bytes": int(wire),
            "collectives": int(n_coll),
            "tp_from": self.tp,
            "tp_to": target.tp,
            "slots_from": self.num_slots,
            "slots_to": target.num_slots,
        }

    def _move_running(self, target: "ServeEngine", req: Request):
        """Move ONE running request's slot — KV state (slab row or page
        chain) plus host sampling state — into ``target``, booking any
        cross-sharding redistribution into the active comm audit.  The
        shared mechanics of :meth:`migrate_to` (whole-engine drain) and
        :meth:`handoff_to` (per-request prefill->decode disaggregation);
        the caller has validated capacity.  Returns
        ``(src_slot, dst_slot, wire_bytes, collectives, pages_moved)``.

        The source is settled first (its mirrors and cache are then the
        device's); the target need not be: its dispatch in flight did not
        carry the request, and its next one starts both slots from the
        host's columns (``_source``).
        """
        self._settle()
        s_a = req.slot
        pos_a = int(self.cache.pos[s_a])
        pages_a = list(req.pages) if (self.paged and req.pages) else None
        s_b = target.scheduler.adopt_running(req)  # sets req.slot
        if self.paged:
            new_pages = target.pool.alloc(len(pages_a))
            w, c = self._copy_kv_pages(target, pages_a, new_pages)
            target.cache.set_table(s_b, new_pages)
        else:
            w, c = self._copy_kv_slot(target, s_a, s_b)
        # detach from the source AFTER the copy (retire validates the
        # slot mapping, so it must see the request still attached —
        # but adopt_running already rewrote req.slot, so point the
        # validation at the source slot for the handoff)
        req.slot = s_a
        self.scheduler.retire(req)
        req.slot = s_b
        self.cache.retire(s_a)
        if pages_a is not None:
            self.pool.decref(pages_a)
            req.pages = new_pages  # prefix-shared pages become private
        target.cache.admit(s_b, pos_a)
        for arr_a, arr_b in (
            (self._last_tok, target._last_tok),
            (self._temps, target._temps),
            (self._seeds, target._seeds),
            (self._ntok, target._ntok),
            (self._budget, target._budget),
            (self._hist, target._hist),
        ):
            arr_b[s_b] = arr_a[s_a]
        self._source[s_a] = target._source[s_b] = FROM_HOST
        return s_a, s_b, w, c, len(pages_a) if pages_a is not None else 0

    def handoff_to(self, target: "ServeEngine", req: Request) -> dict:
        """Hand ONE prefilled running request — KV pages (or slab row)
        and host sampling state — to ``target``, the DistServe-style
        prefill->decode disaggregation step (docs/serving.md, Fleet).

        Unlike :meth:`migrate_to` this moves a single request between
        two LIVE engines: the source keeps admitting/prefilling (its
        prefix index and remaining slots untouched) and the target keeps
        decoding.  The KV move is the same explicit head-axis
        redistribution, priced by the ``obs/comm.py`` ring model and
        booked into the active comm audit; same-sharded engines move
        pages for free (group 1 — no collective, no wire).  The greedy
        stream continues bit-identically on the target: the handoff
        decides WHERE the request decodes, never what it decodes.
        Returns ``{"from_slot", "to_slot", "wire_bytes", "collectives",
        "pages_moved"}``.
        """
        if target is self:
            raise ValueError("cannot hand a request off to its own engine")
        self._refuse_state_move("handoff_to", target)
        self._settle()  # the request may have finished on the device
        if target._draining:
            raise RuntimeError(
                "handoff target is draining — hand off to a live engine"
            )
        if req.slot is None or not any(
            r is req for r in self.scheduler.running
        ):
            raise ValueError(
                f"request {req.rid} is not running on this engine"
            )
        if self.paged != target.paged:
            raise RuntimeError(
                "cannot hand off between slab and paged engines — KV "
                "layouts are not interconvertible in place"
            )
        if self.max_len != target.max_len:
            raise RuntimeError(
                f"KV geometry mismatch: source max_len {self.max_len} "
                f"!= target max_len {target.max_len}"
            )
        if self.paged and self.page_size != target.page_size:
            raise RuntimeError(
                f"page-size mismatch: source {self.page_size} != "
                f"target {target.page_size}"
            )
        if self.kv_dtype_name != target.kv_dtype_name:
            # a requantization pass could bridge this, but silently
            # changing a stream's cache precision mid-flight would break
            # the bit-stability contract the move advertises
            raise RuntimeError(
                f"KV dtype mismatch: source {self.kv_dtype_name} cache "
                f"!= target {target.kv_dtype_name} — KV moves never "
                "requantize"
            )
        if target.scheduler.free_slot_count < 1:
            raise RuntimeError(
                f"handoff target has no free slot for request {req.rid}"
            )
        if self.paged and len(req.pages or ()) > target.pool.free_count:
            raise RuntimeError(
                f"request {req.rid} holds {len(req.pages or ())} KV "
                f"page(s) but the target pool has only "
                f"{target.pool.free_count} free"
            )
        now = time.monotonic()
        s_a, s_b, wire, n_coll, pages_moved = self._move_running(target, req)
        req.record_event(
            "handoff", ts=now, from_slot=s_a, to_slot=s_b, wire_bytes=wire
        )
        self.metrics.count("requests_handed_off")
        self.metrics.count("handoff_pages_moved", pages_moved)
        self.metrics.count("handoff_wire_bytes", wire)
        self.metrics.count("handoff_collectives", n_coll)
        target.metrics.count("requests_handed_in")
        return {
            "from_slot": s_a,
            "to_slot": s_b,
            "wire_bytes": int(wire),
            "collectives": int(n_coll),
            "pages_moved": int(pages_moved),
        }

    def _refuse_state_move(self, what: str, target: "ServeEngine") -> None:
        if self.recurrent or target.recurrent:
            raise ValueError(
                f"{what}: not supported over {_SLAB_ONLY[STATE]} "
                f"({type(self.model).__name__}): a slot's state would "
                "have to move with its rows, which nothing here tests"
            )

    @staticmethod
    def _kv_unit_sharding(dst, *, lead_none: bool):
        """The sharding of one slot row (``lead_none=False``: the leading
        slot/page dim is dropped) or one page segment (``lead_none=True``:
        the leading dim stays, unsharded) of ``dst`` — what the gathered
        unit is placed to before scattering in, so the ``.at[].set``
        update stays layout-compatible with the target cache."""
        from jax.sharding import NamedSharding, PartitionSpec

        sh = dst.sharding
        if not isinstance(sh, NamedSharding):
            return sh
        spec = list(sh.spec) + [None] * (dst.ndim - len(sh.spec))
        rest = spec[1:]
        return NamedSharding(
            sh.mesh,
            PartitionSpec(*([None] + rest if lead_none else rest)),
        )

    @staticmethod
    def _kv_migration_group(src, dst) -> int:
        """Ring gather group for moving one slot row / page chain between
        two differently-sharded KV arrays.  Dim 0 is the slot/page index
        — never sharded, and sized differently across engines — so the
        group comes from the remaining dims (the head axis under TP),
        per the ``parallel/reshard.py`` split-count model."""
        import math as _math

        from ..parallel.reshard import split_counts

        src_c = split_counts(src.shape, src.sharding)[1:]
        tgt_c = split_counts(dst.shape, dst.sharding)[1:]
        n_src = int(np.prod(src_c)) if src_c else 1
        keep = 1
        for a, b in zip(src_c, tgt_c):
            keep *= _math.gcd(int(a), int(b))
        return max(1, n_src // max(1, keep))

    def _copy_kv_slot(self, target, s_a: int, s_b: int):
        """Move slab slot ``s_a``'s KV rows into ``target`` slot ``s_b``,
        booking the tp redistribution per layer/array.  Iterates each
        layer's FULL entry tuple — ``(k, v)`` or the quantized
        ``(k, v, k_scale, v_scale)`` — so int8 data and its scale rows
        move (and price) together; each array's wire unit comes from its
        own dtype, giving the closed form its dtype factor.  Returns
        (wire_bytes, collectives)."""
        wire = 0
        n_coll = 0
        new_kv = []
        for entry_a, entry_b in zip(self.cache.kv, target.cache.kv):
            pair = []
            for src, dst in zip(entry_a, entry_b):
                g = self._kv_migration_group(src, dst)
                unit = int(np.prod(src.shape[1:])) * np.dtype(
                    src.dtype
                ).itemsize
                if g > 1:
                    record_collective(
                        "all_gather",
                        self.tp_axis,
                        payload_bytes=unit,
                        axis_size=g,
                    )
                    wire += unit * (g - 1) // g
                    n_coll += 1
                row = jax.device_put(
                    src[s_a], self._kv_unit_sharding(dst, lead_none=False)
                )
                out = dst.at[s_b].set(row)
                # re-assert the cache layout: the scatter result must not
                # drift to a layout that would recompile the decode jit
                pair.append(jax.device_put(out, dst.sharding))
            new_kv.append(tuple(pair))
        target.cache.kv = new_kv
        return wire, n_coll

    def _copy_kv_pages(self, target, pages_a: List[int], pages_b: List[int]):
        """Move a page chain between paged pools (one gather/scatter per
        layer/array over the whole chain — scale arrays included for
        quantized pools, per-array dtype pricing as in
        :meth:`_copy_kv_slot`).  Returns (wire_bytes, collectives)."""
        idx_a = jnp.asarray(pages_a, jnp.int32)
        idx_b = jnp.asarray(pages_b, jnp.int32)
        n = len(pages_a)
        wire = 0
        n_coll = 0
        new_kv = []
        for entry_a, entry_b in zip(self.cache.kv, target.cache.kv):
            pair = []
            for src, dst in zip(entry_a, entry_b):
                g = self._kv_migration_group(src, dst)
                unit = int(np.prod(src.shape[1:])) * np.dtype(
                    src.dtype
                ).itemsize
                if g > 1 and n:
                    record_collective(
                        "all_gather",
                        self.tp_axis,
                        payload_bytes=unit,
                        count=n,
                        axis_size=g,
                    )
                    wire += (unit * (g - 1) // g) * n
                    n_coll += 1
                seg = jax.device_put(
                    src[idx_a], self._kv_unit_sharding(dst, lead_none=True)
                )
                out = dst.at[idx_b].set(seg)
                pair.append(jax.device_put(out, dst.sharding))
            new_kv.append(tuple(pair))
        target.cache.kv = new_kv
        return wire, n_coll

    def finished_requests(self) -> List[Request]:
        """The bounded finished-request history (newest last): each entry
        carries the full lifecycle event log and the exact timestamps the
        aggregate histograms were fed from."""
        return list(self._finished)

    def dump_trace(self, path: str) -> str:
        """Export the host trace as a catapult/Perfetto ``traceEvents``
        JSON: the global tracer's spans (engine dispatches, scheduler,
        page pool, anything else instrumented in-process) plus one
        thread row per finished request (queued/prefill/decode spans +
        lifecycle instants).  Complements — never replaces — a
        ``jax.profiler`` trace of the same run (docs/observability.md).
        Request rows are exported even when tracing was disabled
        (lifecycle events are always recorded); enable tracing to get
        the dispatch spans alongside them."""
        tracer = get_tracer()
        return tracer.export(
            path, extra_events=request_trace_events(self._finished)
        )

    def num_compiled_programs(self) -> Optional[int]:
        """Compiled executables behind THIS engine's serving programs —
        the dispatch-discipline invariant tests pin (one prefill per
        bucket used + one decode per ``decode_chunk`` value used).  Other
        engines on the same model (the jit store lives on the model) are
        excluded when their static keys differ; engines sharing
        ``(num_slots, max_len, top_k, top_p)`` but not ``decode_chunk``
        share the count, one decode program each.  On the CPU mesh this equals the program count; on
        donation-capable backends each program may carry a second
        executable from the one-time donated-carry layout recompile
        (CLAUDE.md) — the invariant is that the count is STABLE after
        warmup (late admissions never compile), not a particular
        absolute.  Returns None when jit cache introspection
        (``_cache_size``, a private jax API) is unavailable — a count
        that silently assumed one-compile-per-program would let a
        per-step retrace regression pass the pinned invariant."""
        static = self._static_key()
        total = 0
        jits = list(self.model.__dict__.get("_serve_jit_cache", {}).items())
        if self._stream_program is not None:
            # the streaming persistent program lives on the ENGINE (its
            # callback sink is this engine); count it with the rest
            jits.append((("stream",) + static, self._stream_program))
        for key, f in jits:
            if key[-len(static):] != static:
                continue
            size = jit_cache_size(f)
            if size is None:
                return None
            total += size
        return total

    def _new_metrics(self, quant_err_max, quant_err_rms) -> ServeMetrics:
        """A fresh :class:`ServeMetrics` with this engine's geometry and
        footprint gauges: the per-token KV footprint across all layers
        that hold rows, scales included (the quantization win the gauges
        make visible), and beside it what a slot holds of recurrent
        state."""
        cache = self.cache
        rows = (
            self.num_pages * self.page_size
            if self.paged
            else self.num_slots * self.max_len
        )
        state = cache.state_slot_bytes
        return ServeMetrics(
            self.num_slots,
            num_pages=self.num_pages,
            ring_capacity=self.ring_capacity,
            speculate=self.speculate or None,
            kv_cache_bytes=cache.nbytes,
            kv_bytes_per_token=(cache.nbytes - state * self.num_slots) // rows,
            kv_quant_err_max=quant_err_max,
            kv_quant_err_rms=quant_err_rms,
            kv_row_bytes=cache.kv_row_bytes,
            state_slot_bytes=state or None,
        )

    def reset_metrics(self) -> ServeMetrics:
        """Rebind ``self.metrics`` to a fresh :class:`ServeMetrics` with
        THIS engine's geometry (slots, pages, ring, speculate) — the one
        correct way to reset between bench passes; hand-constructing the
        object would silently drop the paged/persistent/speculative
        gauge families."""
        self.metrics = self._new_metrics(
            self.metrics.kv_quant_err_max, self.metrics.kv_quant_err_rms
        )
        return self.metrics

    # -- streamed tail (persistent mode, opt-in) -------------------------

    def _build_stream_cb(self):
        """The streamed tail's host callback: an unordered
        ``io_callback`` into :meth:`_on_stream`."""
        self.stream_supported = "io_callback"

        def stream(tok, live, it):
            io_callback(self._on_stream, None, tok, live, it, ordered=False)

        return stream

    def _on_stream(self, toks, live, it) -> None:
        # host side of the streamed tail.  Runs on a jax runtime thread
        # mid-loop: append-only + counter bump (GIL-atomic enough); the
        # drain consumes the buffer under the engine's single-threaded
        # step() discipline.  Timestamps feed first-token latency; the
        # ring stays the authoritative token path.
        self._stream_events.append(
            (
                time.monotonic(),
                np.asarray(toks).copy(),
                np.asarray(live).copy(),
                int(it),
            )
        )
        self.metrics.count("stream_callbacks")

    # -- the two compiled programs ---------------------------------------

    def _static_key(self) -> tuple:
        # page_size keys the cache LAYOUT: a paged and a slab engine on
        # the same model must never share (or co-count) programs.  The
        # mesh fingerprint (axis names/sizes + device ids) keys the SPMD
        # partitioning: a tp=2 program and a single-chip program on the
        # same model have different out_shardings baked in and must
        # never collide in the shared jit store
        if self.mesh is None:
            mesh_key = None
        else:
            mesh_key = (
                tuple(
                    (str(a), int(s)) for a, s in self.mesh.shape.items()
                ),
                self.tp_axis,
                tuple(d.id for d in self.mesh.devices.flat),
            )
        # kv_dtype keys the cache REPRESENTATION: an int8 engine's
        # programs carry 4-tuple carries + dequant ops and must never
        # share (or co-count) with a plain engine's on the same model.
        # numerics keys the OBSERVATORY: a digest-carrying program has
        # one extra output and must never collide with the plain one
        return (
            self.num_slots, self.max_len, self.top_k, self.top_p,
            self.page_size, self.kv_dtype, mesh_key, self.numerics,
        )

    def _out_shardings(self, n_scalar: int):
        """The explicit ``out_shardings`` pytree prefix for one serve
        program: the (donated) KV carry keeps the cache's head-axis
        sharding, the ``n_scalar`` sampled outputs (token / ring / valid
        / cursor) come back replicated.  None when the cache has no
        NamedSharding placement — single-device programs stay exactly as
        before.  With numerics on, every program carries one trailing
        ``{site: digest}`` dict output: a single replicated leaf covers
        it via jit's out_shardings pytree-prefix semantics."""
        if self._kv_sharding is None:
            return None
        n_extra = 1 if self.numerics else 0
        return (
            (self._kv_sharding,)
            + (self._repl_sharding,) * (n_scalar + n_extra)
        )

    def _harvest_numerics(self) -> None:
        """Fold every parked dispatch digest into the book — called
        ONLY right after an existing ``host_syncs`` accounting point,
        where the dispatch's outputs are already materialized (the
        device_get here is a host copy of ready buffers, never a new
        sync).  Also the drift gate: a KV dequant error above the
        round-to-nearest bound ``s/2`` (``s`` = the max power-of-two
        scale the scale-row digest saw) is a real quantizer invariant
        violation and raises ONE flight anomaly per engine."""
        if not self._pending_digests:
            return
        pend, self._pending_digests = self._pending_digests, []
        try:
            for tree in jax.device_get(pend):
                self.numerics_book.update_tree(tree)
            book = self.numerics_book
            err = book.digest("kv_quant_err")
            if err is not None and err.count:
                self.metrics.observe_kv_quant(err.max_abs, err.rms)
                sc = book.digest("kv_quant_scale")
                bound = 0.5 * sc.max_abs if sc is not None else None
                if (
                    bound
                    and err.max_abs > bound * (1.0 + 1e-6)
                    and not self._kv_quant_alarmed
                ):
                    self._kv_quant_alarmed = True
                    from ..obs.flight import get_flight_recorder

                    get_flight_recorder().record(
                        "anomaly",
                        anomaly="kv_quant_err",
                        err_max=float(err.max_abs),
                        bound=float(bound),
                    )
            book.emit_counter_tracks(get_tracer())
        except Exception:  # pragma: no cover - telemetry must not kill
            pass  # serving; a failed harvest loses a window, not a run

    def _prefill_program(self, bucket: int):
        model, sampler = self.model, self._sampler
        num_on, moe_counts = self.numerics, self._moe_counts
        # a model that can apply its head to one position is asked for
        # the sampled one only: no (bucket, vocab) array in the program
        one_row = bool(getattr(model, "prefill_logits_at", False))

        def build(params, kv, firsts, tokens, true_len, slot, temp, seed):
            def body():
                slab = model.init_cache(1, bucket)
                logits, slab = functional_call(
                    model, params, (tokens, slab, 0),
                    {"logits_at": true_len - 1} if one_row else None,
                    method="forward_cached",
                )
                if not one_row:
                    logits = jax.lax.dynamic_slice_in_dim(
                        logits, true_len - 1, 1, axis=1
                    )
                last = tap("logits", logits[:, 0, :])
                tok = sampler(last, temp, seed, jnp.zeros((1,), jnp.int32))
                return write_slot(kv, slab, slot), *_first(firsts, slot, tok)

            if moe_counts:
                with moe_count_tape() as tape:
                    out = body()
                return (*out, tape_totals(tape))
            return _taped(num_on, body)

        # the kv slab is donated: self.cache.kv is rebound to the output
        # immediately, so the input buffer is dead — without aliasing,
        # every prefill would copy the full multi-GB slot cache (and peak
        # at 2x its footprint).  The dispatch discipline (two programs
        # per token cycle) is unchanged, but on donation-capable
        # backends each program settles at TWO executables: the
        # donated-carry layout recompile on its second call (CLAUDE.md).
        # num_compiled_programs() therefore reads 2 on the CPU mesh
        # (donation is a no-op there) and up to 4 once warm on TPU —
        # stable either way; the invariant tests pin stability, not a
        # backend-specific absolute.
        return _cached_jit(
            model,
            "_serve_jit_cache",
            ("serve_prefill", bucket) + self._static_key(),
            build,
            donate_argnums=(1,),
            out_shardings=self._out_shardings(2),
        )

    def _prefill_warm_program(self, bucket: int):
        """Warm SLAB prefill (chunked prefill's mid-cache chunks): gather
        the slot's row from the engine cache, run the chunk's tokens
        against it at a TRACED ``cache_pos`` (the jnp attention band —
        ``cached_attention``'s flash fast path needs a static 0), sample
        from the chunk's last real position, and write the whole updated
        row back.  One program per bucket, shared across chunk positions
        and slots.  The sampled token only matters for the FINAL chunk
        (it is the request's first token, sampler step 0 — identical to
        the unchunked program's); intermediate chunks discard it."""
        model, sampler, max_len = self.model, self._sampler, self.max_len
        num_on, kv_heads = self.numerics, self.cache.kv_heads

        def build(params, kv, firsts, tokens, cache_pos, true_len, slot,
                  temp, seed):
            def body():
                def row(c):
                    return jax.lax.dynamic_slice(
                        c, (slot, 0, 0), (1, max_len, c.shape[2])
                    )

                # the model gets the slot's row with its head axis back
                # (a view of the one row, not of the cache); quantized
                # caches slice data + scale rows and hand over a
                # dequantized pair; write_slot requantizes on the way
                # back (bit-stable for untouched rows — power-of-two
                # scales, serve/kv_cache.py)
                view = [
                    heads_view([row(a) for a in e], kv_heads) for e in kv
                ]
                logits, view = functional_call(
                    model, params, (tokens, view, cache_pos),
                    method="forward_cached",
                )
                last = tap("logits", jax.lax.dynamic_slice_in_dim(
                    logits, true_len - 1, 1, axis=1
                )[:, 0, :])
                tok = sampler(last, temp, seed, jnp.zeros((1,), jnp.int32))
                return write_slot(kv, view, slot), *_first(firsts, slot, tok)

            return _taped(num_on, body)

        return _cached_jit(
            model,
            "_serve_jit_cache",
            ("serve_prefill_warm", bucket) + self._static_key(),
            build,
            donate_argnums=(1,),
            out_shardings=self._out_shardings(2),
        )

    def _paged_prefill_program(self, bucket: int, warm: bool):
        """Paged prefill: gather the slot's logical cache through its
        page-table row, run the (suffix) tokens against it, sample from
        the last real position, and scatter ONLY the suffix-bucket rows
        back into the pools (shared prefix pages are never rewritten —
        handoff is the table row itself).

        Two program families per bucket: **cold** passes a static
        ``cache_pos=0`` (so ``cached_attention``'s flash-prefill fast
        path still applies on TPU, exactly as in the slab engine) and
        **warm** a traced page-aligned prefix length (mid-cache chunked
        prefill, the jnp path).  Bucket padding may scatter garbage rows
        past the request's allocated pages; the table routes those onto
        the scratch page, where nothing ever reads them.
        """
        model, sampler, ps = self.model, self._sampler, self.page_size
        num_on, kv_heads = self.numerics, self.cache.kv_heads

        def build_warm(params, kv, firsts, pt_row, tokens, pfx_len, true_len,
                       slot, temp, seed):
            def body():
                view = paged_view(kv, pt_row, kv_heads)
                logits, view = functional_call(
                    model, params, (tokens, view, pfx_len),
                    method="forward_cached",
                )
                last = tap("logits", jax.lax.dynamic_slice_in_dim(
                    logits, true_len - 1, 1, axis=1
                )[:, 0, :])
                tok = sampler(last, temp, seed, jnp.zeros((1,), jnp.int32))
                out = paged_scatter_rows(
                    kv, view, pt_row, ps, pfx_len, bucket
                )
                return out, *_first(firsts, slot, tok)

            return _taped(num_on, body)

        def build_cold(params, kv, firsts, pt_row, tokens, true_len, slot,
                       temp, seed):
            def body():
                view = paged_view(kv, pt_row, kv_heads)
                logits, view = functional_call(
                    model, params, (tokens, view, 0),
                    method="forward_cached",
                )
                last = tap("logits", jax.lax.dynamic_slice_in_dim(
                    logits, true_len - 1, 1, axis=1
                )[:, 0, :])
                tok = sampler(last, temp, seed, jnp.zeros((1,), jnp.int32))
                out = paged_scatter_rows(
                    kv, view, pt_row, ps, jnp.int32(0), bucket
                )
                return out, *_first(firsts, slot, tok)

            return _taped(num_on, body)

        # pools donated like the slab (engine rebinds before the sync)
        return _cached_jit(
            self.model,
            "_serve_jit_cache",
            ("serve_prefill_paged", bucket, warm) + self._static_key(),
            build_warm if warm else build_cold,
            donate_argnums=(1,),
            out_shardings=self._out_shardings(2),
        )

    def _decode_program(self):
        """The fused K-step decode program (``_make_fused_decode``): one
        per ``(decode_chunk, eos_token)`` — both are baked into the scan
        body (the on-device finish mask needs the EOS id; the scan length
        is the chunk).  The default single-K engine therefore still holds
        the one-decode-program invariant.  Paged engines pass the page
        tables as one extra dynamic input to the same builder (the
        static key's ``page_size`` keeps the layouts' programs
        apart)."""
        build = _make_fused_decode(
            self.model,
            self._sampler,
            eos_token=self.eos_token,
            max_len=self.max_len,
            decode_chunk=self.decode_chunk,
            numerics=self.numerics,
            moe_counts=self._moe_counts,
        )
        return _cached_jit(
            self.model,
            "_serve_jit_cache",
            ("serve_decode", self.decode_chunk, self.eos_token)
            + self._static_key(),
            build,
            donate_argnums=(1,),  # kv slab: same aliasing as prefill
            out_shardings=self._out_shardings(2),  # the block, the carry
        )

    def _persistent_program(self):
        """The persistent whole-loop decode program
        (``_make_persistent_decode``): the SAME fused body inside a
        ``lax.while_loop``, one per ``(ring_capacity, eos_token)``.
        STREAMING engines cache their program on the engine itself, not
        in the model's shared jit store: the streamed tail closes over
        this engine (its host sink), so parking it on the model would
        pin every discarded streaming engine — KV slab included — for
        the model's lifetime; an engine-local jit dies with the
        engine."""
        if self._stream_cb is not None:
            if self._stream_program is None:
                build = _make_persistent_decode(
                    self.model,
                    self._sampler,
                    eos_token=self.eos_token,
                    max_len=self.max_len,
                    ring_capacity=self.ring_capacity,
                    stream_cb=self._stream_cb,
                    numerics=self.numerics,
                )
                kwargs = {}
                if self._out_shardings(3) is not None:
                    kwargs["out_shardings"] = self._out_shardings(3)
                self._stream_program = jax.jit(
                    build, donate_argnums=(1,), **kwargs
                )
            return self._stream_program
        build = _make_persistent_decode(
            self.model,
            self._sampler,
            eos_token=self.eos_token,
            max_len=self.max_len,
            ring_capacity=self.ring_capacity,
            stream_cb=None,
            numerics=self.numerics,
        )
        return _cached_jit(
            self.model,
            "_serve_jit_cache",
            ("serve_decode_persistent", self.ring_capacity, self.eos_token)
            + self._static_key(),
            build,
            donate_argnums=(1,),  # kv slab: same aliasing as prefill
            out_shardings=self._out_shardings(3),
        )

    def _spec_decode_program(self):
        """The fused SPECULATIVE decode program
        (``_make_fused_spec_decode``): one per ``(decode_chunk,
        eos_token, speculate, spec_ngram)``.  A distinct key prefix from
        the one-token program — a ``speculate=0`` engine never pays for
        (or collides with) the spec body; the shared static-key suffix
        keeps ``num_compiled_programs()`` counting both families."""
        build = _make_fused_spec_decode(
            self.model,
            self._sampler,
            eos_token=self.eos_token,
            max_len=self.max_len,
            decode_chunk=self.decode_chunk,
            speculate=self.speculate,
            ngram=self.spec_ngram,
            numerics=self.numerics,
        )
        return _cached_jit(
            self.model,
            "_serve_jit_cache",
            (
                "serve_decode_spec", self.decode_chunk, self.eos_token,
                self.speculate, self.spec_ngram,
            )
            + self._static_key(),
            build,
            donate_argnums=(1,),  # kv slab: same aliasing as prefill
            out_shardings=self._out_shardings(2),
        )

    def _spec_persistent_program(self):
        """The persistent SPECULATIVE decode program
        (``_make_persistent_spec_decode``): the spec body under the same
        while-loop fixpoint drive, one ring row per ITERATION (worth up
        to ``speculate + 1`` tokens) — drains still bound syncs."""
        build = _make_persistent_spec_decode(
            self.model,
            self._sampler,
            eos_token=self.eos_token,
            max_len=self.max_len,
            ring_capacity=self.ring_capacity,
            speculate=self.speculate,
            ngram=self.spec_ngram,
            numerics=self.numerics,
        )
        return _cached_jit(
            self.model,
            "_serve_jit_cache",
            (
                "serve_decode_persistent_spec", self.ring_capacity,
                self.eos_token, self.speculate, self.spec_ngram,
            )
            + self._static_key(),
            build,
            donate_argnums=(1,),  # kv slab: same aliasing as prefill
            out_shardings=self._out_shardings(3),
        )

    # -- internals -------------------------------------------------------

    def _bucket_for(self, length: int) -> int:
        for b in self.prefill_buckets:
            if b >= length:
                return b
        # submit() pre-validates against prefill_buckets[-1], so reaching
        # here means a caller bypassed it — same clear error either way,
        # raised host-side, never from inside the prefill jit
        raise ValueError(
            f"prompt length {length} exceeds the largest prefill bucket "
            f"({self.prefill_buckets[-1]})"
        )

    def _prefill_chunks(self, start0: int, total: int) -> list:
        """Split ``total`` prefill tokens starting at cache position
        ``start0`` into ``(start, length)`` chunks of at most
        ``chunked_prefill`` tokens.  Every non-final chunk is exactly the
        threshold (its bucket is the threshold itself — validated to be
        a real bucket) and always fits: ``start + C <= start0 + total <=
        max_len``.  The FINAL chunk's padded bucket may overrun
        ``max_len`` (a short tail bucket-padded past the end would make
        the write clamp onto real rows); such a tail is folded into its
        predecessor, terminating — in the worst case — at the one-chunk
        split, whose bucket fit was already guaranteed at admission."""
        c = self.chunked_prefill
        chunks = []
        s = 0
        while s < total:
            ln = min(c, total - s)
            chunks.append((start0 + s, ln))
            s += ln
        while len(chunks) > 1:
            st, ln = chunks[-1]
            if st + self._bucket_for(ln) <= self.max_len:
                break
            pst, pln = chunks[-2]
            chunks[-2:] = [(pst, pln + ln)]
        return chunks

    def _interleave_decode(self, req: Request) -> None:
        """One decode dispatch between two prefill chunks, skipping the
        half-prefilled request — the whole point of chunked prefill:
        active slots emit tokens while the long prompt is still landing.
        Skipped when this request is the only one running (nothing to
        un-stall)."""
        if len(self.scheduler.running) > 1:
            self.metrics.count("prefill_interleaved_dispatches")
            self._decode_step(skip=req)

    def _make_admission_gate(self):
        """The composed admission predicate ``Scheduler.admit`` runs on
        the FCFS head: the HBM-budget gate FIRST (a request the device
        cannot hold must not grab pages), then the paged engine's
        free-pages gate.  The closure names its refusal cause via the
        ``why`` attribute the scheduler reads into the request's
        lifecycle log — the ISSUE 8 named-reason contract."""

        def gate(req: Request) -> bool:
            gate.why = "gate"
            if self._draining:
                # checked before hbm/pages so a draining refusal never
                # reserves anything the migration would have to unwind
                gate.why = "draining"
                return False
            if self.hbm_budget is not None:
                plan = self.memory_plan()
                if plan["fits"] is False:
                    gate.why = "hbm_budget"
                    self.metrics.count("admissions_rejected_hbm")
                    return False
            if self.paged:
                return self._page_gate(req)
            return True

        gate.why = "gate"
        return gate

    def memory_plan(self, budget_bytes: Optional[int] = None) -> dict:
        """The live HBM capacity plan (``obs.memory.capacity_plan``):
        per-device weights + the KV slab/pools + the worst per-program
        temp bytes the cost observatory has on record, against
        ``budget_bytes`` / ``self.hbm_budget`` / the device's PJRT
        limit (in that order).  This is what the admission gate refuses
        on; bench_serve embeds it per phase.  With cost cards disabled
        the temp component is 0 — the plan then under-counts dispatch
        transients and says so via the component being absent.

        The weights/KV components are invariant after construction and
        cached: the admission gate runs this per queued-head tick, and
        a per-tick walk of a 7B param tree would put model-size-scaled
        host work on the serve hot path."""
        from ..obs import memory as obs_memory

        if self._static_footprint is None:
            # PER-SHARD accounting on both components: tree_device_bytes
            # is the largest addressable shard per leaf, so TP-sharded
            # weights and the head-sharded cache each contribute their
            # 1/tp slice — the number a single device must actually hold,
            # which is what makes the admission gate meaningful for
            # models bigger than one chip's HBM
            self._static_footprint = {
                "weights": obs_memory.tree_device_bytes(self.params),
                "kv_cache": obs_memory.tree_device_bytes(
                    [e[:2] for e in self.cache.kv]
                ),
            }
            if self.kv_quantized:
                # int8 engines split the pool: "kv_cache" is the int8
                # data alone (the component that halves exactly vs a
                # bf16 cache — the bench A/B's strict pin) and the f32
                # scale sidecar is priced separately
                self._static_footprint["kv_scales"] = (
                    obs_memory.tree_device_bytes(
                        [e[2:] for e in self.cache.kv]
                    )
                )
        components = dict(self._static_footprint)
        temp = self.cost_book.max_temp_bytes()
        if temp:
            components["program_temp"] = temp
        if budget_bytes is None:
            budget_bytes = self.hbm_budget
        plan = obs_memory.capacity_plan(
            components, budget_bytes=budget_bytes
        )
        # name the cache dtype on the plan itself (components stay
        # numeric — capacity_plan drops non-numeric values), so an
        # over-budget refusal under mixed-dtype fleets is attributable
        plan["kv_cache_dtype"] = self.kv_dtype_name
        return plan

    # -- cost observatory / stall watchdog --------------------------------

    def _ensure_card(self, name: str, program, args) -> None:
        """Capture ``program``'s CostCard at its first dispatch (the
        args are still host-live — lowering reads avals only, so the
        donated KV slab is safe).  One card per program name; the
        donated-carry second executable (CLAUDE.md) is the same HLO
        with different layouts and is deliberately not re-carded.  A
        cost probe must never fail a dispatch."""
        if not self._cards_on or name in self._carded:
            return
        self._carded.add(name)
        try:
            from ..obs.cost import compute_cost_card

            compute_cost_card(
                program, *args, name=name, book=self.cost_book
            )
        except Exception:
            pass

    def _watch(self, name: str):
        """The stall-watchdog guard for one dispatch+sync region (a
        no-op context when no watchdog is configured)."""
        if self.watchdog is None:
            return contextlib.nullcontext()
        return self.watchdog.arm(name)

    def _record_tp_collectives(self, n_tokens: int, steps: int = 1) -> None:
        """Closed-form per-layer all-reduce accounting for the mesh path,
        recorded into any active :func:`obs.comm.comm_audit`.  GSPMD
        inserts the collectives at compile time, invisibly to Python-
        level tracing (obs/comm.py module doc), so the engine records the
        Megatron closed form at dispatch time — exactly like the training
        TP leg's ``allreduce_linear`` pins: one all-reduce of the
        ``(n_tokens, dim)`` activation per ROW-PARALLEL projection
        (``wo`` + ``w_down`` = 2 per block), per on-device step.  The
        lm_head gather and sampler reductions are tiny and deliberately
        not modeled.  No-op off the mesh path, on tp=1 meshes, and for
        models whose config hides the block geometry."""
        if self.tp <= 1 or self._tp_geom is None:
            return
        n_layers, dim = self._tp_geom
        itemsize = 4  # f32 activations (the serve models' param dtype)
        record_collective(
            "all_reduce",
            self.tp_axis,
            payload_bytes=int(n_tokens) * dim * itemsize,
            count=2 * n_layers * int(steps),
            axis_size=self.tp,
        )

    def _page_gate(self, req: Request) -> bool:
        """Paged admission gate (run by ``Scheduler.admit`` on the FCFS
        head): match the prompt against the prefix index, reserve the
        shared pages (incref) plus fresh pages for the rest of the
        request's page-aligned footprint, evicting LRU unreferenced
        prefixes under pressure.  False (pages short even after
        eviction) blocks the line until running requests retire; the
        reservation is stashed on the request for ``_prefill_request``.
        """
        ps = self.page_size
        hit: list = []
        if self.prefix_index is not None:
            hit = self.prefix_index.match(req.prompt)
            # the suffix prefill writes view rows [P, P + bucket): shrink
            # the hit until that span fits the slot geometry (P = 0
            # always does — cold prefill is the no-hit case)
            while hit and (
                len(hit) * ps
                + self._bucket_for(req.prompt.size - len(hit) * ps)
                > self.max_len
            ):
                hit.pop()
        need_total = -(-(req.prompt.size + req.max_new_tokens) // ps)
        need_new = need_total - len(hit)
        self.pool.incref(hit)  # pin before eviction can consider them
        if self.pool.free_count < need_new and self.prefix_index is not None:
            self.metrics.count(
                "pages_evicted",
                self.prefix_index.evict(
                    self.pool, need_new - self.pool.free_count
                ),
            )
        if self.pool.free_count < need_new:
            self.pool.decref(hit)
            # the page-pressure rejection signal the fleet router polls
            # (one tick per refused admit, like admissions_rejected_hbm)
            self.metrics.count("admissions_rejected_pages")
            return False
        req.pages = hit + self.pool.alloc(need_new)
        req.prefix_len = len(hit) * ps
        return True

    def _prefill_request(self, req: Request, slot: int) -> None:
        tok = self._dispatch_prefill(req, slot)
        self.cache.admit(slot, req.prompt.size)
        self._temps[slot] = req.temperature
        self._seeds[slot] = req.seed
        self._ntok[slot] = 1
        self._budget[slot] = req.max_new_tokens
        if self.speculate:
            # seed the draft history with the prompt; generated tokens
            # append at their stream index as the walks record them
            self._hist[slot] = 0
            self._hist[slot, : req.prompt.size] = req.prompt
        now = time.monotonic()
        self.metrics.count("prefill_calls")
        self.metrics.count("requests_admitted")
        self.metrics.queue_wait_s.record(
            (req.admitted_at or now) - req.submitted_at
        )
        self._source[slot] = FIRST_ON_DEVICE
        if self._persistent or self._lags:
            # NO host sync here: the device scalar parks until the next
            # ring drain, or the end of this step on a row that lags (the
            # program recomputes the finish bit on-device, so an EOS or
            # instantly-over-budget first token still freezes its slot
            # before iteration 0)
            self._pending_first[slot] = tok
            return
        self.metrics.count("host_syncs")  # the dispatch's token fetch
        self._harvest_numerics()
        self._record_first(req, tok, now)
        self._check_finished(req, tok, now)
        self._record_drain()

    def _record_first(self, req: Request, tok: int, now: float) -> None:
        """First-token bookkeeping shared by the chunked path (at
        prefill, post-sync) and the persistent path (at drain, or at a
        pre-drain deadline flush).  The aggregate histograms are fed
        from the request's OWN lifecycle timestamps (not a second clock
        read), so the per-request view (RequestResult.ttft_s, the
        Perfetto request track) and the aggregates provably agree —
        pinned in tests/test_obs.py."""
        self._last_tok[req.slot] = tok
        if self.speculate:
            # the first token's stream index is the prompt length — the
            # slot's cache position at record time (no advance has run)
            p = int(self.cache.pos[req.slot])
            if p < self.max_len:
                self._hist[req.slot, p] = tok
        req.first_token_at = now
        req.record_event("first_token", ts=now)
        req.generated.append(tok)
        self.metrics.count("tokens_generated")
        self.metrics.ttft_s.record(req.first_token_at - req.submitted_at)

    @staticmethod
    def _sampling_args(req: Request) -> tuple:
        """A prefill program's last two arguments: the request's
        temperature and seed as one-element HOST arrays.  Like every
        small argument of a dispatch they cross to the device inside the
        program call (its own argument path), not through a
        ``jnp.asarray`` each beforehand.  The dtypes are written out: a
        Python number would be weakly typed and compile a second
        program."""
        return (
            np.asarray([req.temperature], np.float32),
            np.asarray([req.seed], np.int32),
        )

    def _dispatch_prefill(self, req: Request, slot: int):
        """The prefill of one admitted request, whole or in chunks, slab
        or paged: one dispatch per chunk, written once.  Returns the
        first token (a device scalar where its fetch is deferred: to the
        drain in persistent mode, to ``_first_tokens`` on a row that
        lags, whose ``prefill_s`` record waits with it).

        A paged engine consumes the admission gate's page reservation:
        it points the slot's table at the chain, prefills ONLY the
        uncached suffix (tokens past the page-aligned prefix hit — the
        hit is the prefill compute, and tokens, the cache saved), and
        adopts the request's full-prompt pages into the prefix index.

        A prompt (slab) or suffix (paged) over ``chunked_prefill`` takes
        the CHUNKED way: ``_prefill_chunks``' pieces, with one decode
        dispatch interleaved between consecutive ones
        (``_interleave_decode``, skipping this half-prefilled request).
        Anything else is the chunk list of length one.  The slot is
        PARKED at row ``max_len - 1`` for the duration: the interleaved
        decode program rewrites every slot's current row, inactive slots
        included, and the slot's stale position could land that garbage
        inside an already-written chunk.  Row ``max_len - 1`` is safe:
        prefill never claims it (``prompt <= max_len - max_new <
        max_len``), and the slot's own decode write replaces it in the
        same dispatch that first makes it visible (the stale-row
        argument of kv_cache.py, applied to one designated row).  On the
        slab the row is private to its slot; paged, the parked write
        routes through the slot's table to its LAST entry — the scratch
        page for a short chain, else the request's own tail page, never
        a shared prefix page (the hit is at most the prompt, which sits
        strictly below ``max_len``).  ``cache.admit`` restores the true
        position after the final chunk."""
        pfx = req.prefix_len if self.paged else 0
        total = req.prompt.size - pfx
        chunked = (
            self.chunked_prefill is not None and total > self.chunked_prefill
        )
        # the tail fold may leave ONE chunk: still the chunked way
        chunks = self._prefill_chunks(pfx, total) if chunked else [(pfx, total)]
        fields = {"bucket": self._bucket_for(chunks[0][1]), "cold": pfx == 0}
        if self.paged:
            fields["prefix_hit_tokens"] = pfx
            self.cache.set_table(slot, req.pages)
        if chunked:
            fields["chunks"] = len(chunks)
            self.cache.pos[slot] = self.max_len - 1  # park (see docstring)
            self.metrics.count("chunked_prefills")
        req.record_event("prefill", **fields)
        tok = None
        for i, (start, ln) in enumerate(chunks):
            if i > 0:
                self._interleave_decode(req)
            bucket = self._bucket_for(ln)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :ln] = req.prompt[start : start + ln]
            if chunked:
                req.record_event("prefill_chunk", start=start, bucket=bucket)
            program, name, args = self._prefill_call(
                req, slot, start, ln, padded
            )
            self._ensure_card(name, program, args)
            # a row that lags keeps the seconds: the prefill's one record
            # is made when its token arrives (``_first_tokens``)
            sink = (
                functools.partial(self._prefill_host_s.__setitem__, slot)
                if self._lags else None
            )
            # the span ends in the first token's sync where that is not
            # deferred: a wait of the cycle's account, not scheduling
            syncs = i == len(chunks) - 1 and not (
                self._persistent or self._lags
            )
            key = "first_wait" if syncs else None
            with self._phase("prefill", sink, key), self._watch(name):
                self._count_if_starved("prefill")
                out = program(*args)
                # rebind BEFORE the host sync: the dispatch donated the
                # old slab (or pools), so if the sync raises the engine
                # must already hold the live output, not a deleted buffer
                self.cache.kv, tok, self._firsts = out[:3]
                self._last_out = tok
                if self.numerics:
                    self._pending_digests.append(out[-1])
                if self._moe_counts:
                    # the cold slab program's; an expert model is served
                    # through no other (the constructor's refusals)
                    self.metrics.add_device_counts("prefill", out[3])
                if syncs:
                    tok = int(np.asarray(tok))  # host sync: first token exists
            # only what was computed: a prefix hit's tokens are not
            self.metrics.count("tokens_prefilled", bucket)
            if chunked:
                self.metrics.count("prefill_chunks")
            self._record_tp_collectives(bucket)
        if self.paged:
            self._adopt_prefix(req)
        return tok

    def _prefill_call(
        self, req: Request, slot: int, start: int, ln: int, padded
    ) -> tuple:
        """``(program, card name, args)`` for one prefill chunk: ``ln``
        prompt tokens, bucket-padded in ``padded``, landing at cache
        position ``start``.  COLD (``start == 0``, static in the program,
        so ``cached_attention``'s flash fast path applies) or WARM (a
        traced position: a later chunk, or a paged prefix hit, which is
        why chunked and prefix-hit prefill share programs), slab (the
        slot is an argument) or paged (its table row is)."""
        bucket = padded.shape[1]
        warm = start > 0
        at = (np.int32(start),) if warm else ()
        if self.paged:
            program = self._paged_prefill_program(bucket, warm=warm)
            name = f"serve/prefill/{'warm' if warm else 'cold'}/b{bucket}"
            row = self.cache.page_tables[slot].copy()
            where = (row, padded, *at, np.int32(ln), np.int32(slot))
        else:
            build = self._prefill_warm_program if warm else self._prefill_program
            program = build(bucket)
            name = f"serve/prefill/{'warm/' if warm else ''}b{bucket}"
            where = (padded, *at, np.int32(ln), np.int32(slot))
        args = (
            self.params, self.cache.kv, self._firsts, *where,
            *self._sampling_args(req),
        )
        return program, name, args

    def _adopt_prefix(self, req: Request) -> None:
        """A paged prefill's prefix bookkeeping: hit-rate counters +
        handing the request's full-prompt page-aligned pages to the
        radix index."""
        if self.prefix_index is None:
            return
        ps = self.page_size
        self.metrics.count("prefix_lookup_tokens", int(req.prompt.size))
        self.metrics.count("prefix_hit_tokens", req.prefix_len)
        n_full = req.prompt.size // ps
        self.prefix_index.insert(
            req.prompt[: n_full * ps], req.pages[:n_full], self.pool
        )

    def _decode_args(self) -> tuple:
        """The argument list of a decode dispatch, written once for the
        four decode programs (fused / persistent, each plain or
        speculative): ``(params, kv, [carry, firsts,] state[, hist][,
        page_tables])``.

        ``state`` is the per-slot state packed into ONE host array
        (``generation.pack_slot_state``), and it crosses to the device
        INSIDE the program call, through the dispatch's own argument
        path: one transfer, where seven ``jnp.asarray`` were seven
        Python-level ``device_put``s of 0.28 ms each on the chip's host,
        every step, with the device idle (PERF.md, PR 31).  Every array
        handed over is one nothing writes afterwards — the packed state
        is fresh, and ``_hist`` and the page tables, live mirrors written
        again in ``serve/harvest`` and at admission, go as copies — so no
        ordering of transfer and bookkeeping is relied on.

        The fused one-token program also takes the last dispatch's final
        carry and the prefills' first tokens, device arrays both, and
        ``state`` gains the row that says which slots start from the
        host's column (``_source``: admitted, expired, moved since the
        last dispatch).  With nothing in flight the mirrors ARE the
        device's state and every slot starts from them: the same program,
        the same argument types."""
        cache = self.cache
        carries = _DECODE_VARIANTS[self._persistent, bool(self.speculate)][3]
        source = None
        if carries:
            source, self._source = self._source, np.zeros_like(self._source)
            if self._in_flight is None:
                source = np.maximum(source, FROM_HOST)
        if self._persistent:
            # the ACTIVE mask carries the cache-full rule: positions() is
            # clamped to max_len - 1, so the room check must come from
            # the UNCLAMPED host positions or it could never fire
            # (_make_persistent_decode docstring)
            mask = cache.active & (cache.pos < self.max_len)
        else:
            mask = ~cache.active  # retired slots: finished
        state = pack_slot_state(
            self._last_tok,
            cache.positions(),
            self._temps,
            self._seeds,
            self._ntok,
            self._budget,
            mask,
            source,
        )
        if self._persistent:
            # freshly prefilled slots: their first token exists only on
            # device; splice it into the state's last-token row without a
            # fetch (a tiny host-staged update, no sync).  The state is a
            # device array on EVERY persistent dispatch, tokens pending or
            # none: one argument type, one entry in the jit's dispatch
            # cache.  The index is ARRAY-typed on purpose: a python-int
            # index is a static value baked into the scatter executable,
            # so each distinct slot would compile its own op — a per-slot
            # recompile the recompile watcher flags in the bench's
            # measured window
            state = jnp.asarray(state)
            for slot, dev_tok in self._pending_first.items():
                state = state.at[0, jnp.asarray(slot, jnp.int32)].set(dev_tok)
        args = [self.params, cache.kv]
        if carries:
            args += [self._carry, self._firsts]
        args.append(state)
        if self.speculate:
            args.append(self._hist.copy())
        if self.paged:
            # tiny int32 dynamic input; rewritten host-side at every
            # admit/retire, and only there: pages are freed or reallocated
            # at chunk and drain boundaries, so it is invariant within a
            # dispatch and no frozen in-loop write can land on a page this
            # table doesn't own (a page freed while the dispatch that still
            # names it is in flight is written again only by programs
            # queued behind that dispatch)
            args.append(cache.page_tables.copy())
        return tuple(args)

    def _decode_step(self, skip: Optional[Request] = None) -> None:
        """One decode dispatch, ONE host sync, one walk — whichever of
        the four decode programs this engine runs (``_DECODE_VARIANTS``:
        fused or persistent, each one-token or speculative).

        **The order.**  ``serve/decode_args``: the arguments, and the
        requests and slots the dispatch carries, as they are now (its
        ``riders``).  ``serve/decode``: the dispatch ``D(k)``, then the
        fetch of a token block — ``D(k)``'s own, or on an engine that
        lags (``_lags``) that of ``D(k-1)``, which has been running since
        the previous step while the host walked, returned, was observed,
        scheduled and packed.  Then the step's first tokens
        (``_first_tokens``) and ``serve/harvest``: the walk of the block
        just fetched, over ITS riders — never over ``scheduler.running``
        as it is now: a slot freed by the last walk and admitted again
        since holds, in the block of the dispatch that was in flight, its
        previous tenant's frozen token.  Reading at once is this same
        order with the dispatch settled right after it is issued;
        ``_settle()`` is the second half alone.

        The fused programs run ``decode_chunk`` on-device iterations;
        the persistent ones loop on-device until every slot's finish bit
        sets or the ring fills, and the pending prefill first-tokens ride
        the drain's sync.  Either way the host gets tokens per
        ``(iteration, slot, lane)`` and a count per ``(iteration,
        slot)``: the count is 0 exactly from where the device froze the
        slot (rows past it are rewrites), and a speculative iteration
        emits up to ``speculate + 1`` tokens.  The walk applies the same
        finish rules the device's mask did (``_check_finished``), so the
        host's bookkeeping (positions, token counts, finish reasons,
        metrics) and the device's frozen carries agree iteration for
        iteration, token for token; tokens a request emitted after its
        own finish never exist on the host side, and the slot-iterations
        the device ran past a finish are accounted in
        ``masked_slot_steps``.  A request the ring cut off (budget-bound
        exit) simply stays running and continues from its frozen carry
        at the next dispatch — spanning drains is the persistent analog
        of spanning chunks.  Speculation multiplies tokens per sync, it
        never adds one: ``host_syncs == ring_drains`` either way."""
        persistent, spec = self._persistent, self.speculate
        cycle = self._cycle = self._cycle + 1
        with self._phase("decode_args", cycle=cycle):
            builder, n_out, _, carries = _DECODE_VARIANTS[
                persistent, bool(spec)
            ]
            # not yet cache-admitted: the mid-chunked-prefill request
            # itself (parked, device-frozen) or, fused, a same-batch
            # admit an interleaved dispatch ran ahead of — their tokens
            # start at their own prefill, not here (a persistent one's
            # ride this drain: its first token)
            active = self.cache.active
            riders = [
                (req, req.slot)
                for req in self.scheduler.running
                if req is not skip and (persistent or active[req.slot])
            ]
            program = getattr(self, builder)()
            args = self._decode_args()
            self._stream_events.clear()  # the streamed tail's, if any
            name = "serve/decode{}{}/{}".format(
                "/persistent" if persistent else "",
                f"/spec{spec}" if spec else "",
                f"r{self.ring_capacity}" if persistent
                else f"k{self.decode_chunk}",
            )
            self._ensure_card(name, program, args)
        with self._phase("decode"), self._watch(name):
            # the host busy: the call and the rebinding of its outputs
            with self._phase("dispatch", cycle=cycle):
                self._count_if_starved("decode")
                out = program(*args)
                self.cache.kv = out[0]  # before the sync: old slab was donated
                self._last_out = out[1]
                rest = 1 + n_out  # what follows the fetched outputs
                if carries:
                    self._carry = out[rest]
                    rest += 1
                if self._moe_counts:
                    # the fused one-token program's alone (the
                    # constructor's refusals)
                    self.metrics.add_device_counts("decode", out[rest])
                due = _Flight(
                    out[1 : 1 + n_out],
                    riders,
                    None if persistent else self.decode_chunk,
                    out[-1] if self.numerics else None,
                    cycle,
                )
                if self._lags:  # read the predecessor's block, not this one's
                    due, self._in_flight = self._in_flight, due
                    if due is not None:
                        self.metrics.count("lagged_dispatches")
                # drop the dispatch's handles: the arguments are host arrays
                # but for the old carry, the outputs live on in the flight
                del args, out
            # the host idle: ``serve/wait``
            fetched = self._fetch(due, dispatched=cycle)
        self._land(due, fetched)

    def _settle(self) -> None:
        """Fetch and walk whatever is in flight — a decode dispatch's
        block, first tokens whose fetch was deferred — so that the host
        mirrors, the requests and the cache say what the device holds.
        ``drain``, ``migrate_to`` / ``handoff_to`` (``_move_running``),
        ``step_prefill`` and the end of ``run`` call it before they read
        any of those; ``step`` calls it when it has nothing to issue.
        ``finished_requests()`` and the metrics do not: they report what
        the host has seen.  With nothing in flight it does nothing."""
        due, self._in_flight = self._in_flight, None
        if due is not None or (self._lags and self._pending_first):
            fetched = None
            if due is not None:
                with self._phase("decode"):
                    fetched = self._fetch(due)
            self._land(due, fetched)
        # nothing is queued now: the next dispatch has nothing to overlap
        # and the next block to arrive ends no cycle
        self._nothing_queued = True
        self.metrics.cycles.settled()

    def _fetch(self, flight: Optional[_Flight], dispatched=None):
        """THE host sync of a decode dispatch: its token outputs and, in
        persistent mode, every pending first token together, as ``(host
        arrays, {slot: first token})``.  The first read waits for the
        program; the other copies are in flight behind that wait
        (``device_get`` does the same under a tree walk that costs the
        one-output programs 16 us more than this).  On an engine that
        lags the program has usually ended by now.  ``dispatched`` is
        the decode dispatch issued since the last block arrived, for the
        cycle's record."""
        if flight is None:
            return None  # the first dispatch after a settle: nothing due
        pending = self._pending_first if self._persistent else {}
        leaves = (*flight.outputs, *pending.values())
        with self._phase("wait", cycle=flight.cycle):
            for i in range(1, len(leaves)):
                leaves[i].copy_to_host_async()
            host = [np.asarray(x) for x in leaves]
        # the arrival: where one cycle ends and the next begins
        self.metrics.cycle_arrived(
            flight.cycle, len(flight.riders), dispatched
        )
        self.metrics.count("host_syncs")
        if flight.digests is not None:
            self._pending_digests.append(flight.digests)
        n_out = len(flight.outputs)
        return host[:n_out], dict(zip(pending, host[n_out:]))

    def _first_tokens(self) -> None:
        """On an engine that lags, the first tokens of the requests this
        step admitted, fetched in admission order once the step's decode
        dispatch is queued behind their prefills.  A step that admitted
        returns only when they are on the host: time to first token stays
        a decode step plus a prefill.

        Each wait sits in a ``serve/schedule`` > ``serve/prefill`` span
        pair and completes the prefill's ONE ``prefill_s`` record: its
        dispatch's host seconds plus this wait, which runs from the
        arrival of the previous result to the arrival of its token (its
        device time).  ``schedule_s.total - prefill_s.total`` stays the
        host's own scheduling time."""
        if not (self._lags and self._pending_first):
            return
        with self._phase("schedule"):
            by_slot = {req.slot: req for req in self.scheduler.running}
            while self._pending_first:
                slot = next(iter(self._pending_first))
                dev_tok = self._pending_first.pop(slot)
                held = self._prefill_host_s.pop(slot)
                with self._phase(
                    "prefill",
                    lambda s, held=held: self.metrics.prefill_s.record(
                        held + s
                    ),
                    "first_wait",
                ):
                    tok = int(np.asarray(dev_tok))  # host sync
                self.metrics.count("host_syncs")
                now = time.monotonic()
                req = by_slot[slot]
                self._record_first(req, tok, now)
                self._check_finished(req, tok, now)
            self._record_drain()

    def _land(self, flight: Optional[_Flight], fetched) -> None:
        """What follows a fetch: the step's first tokens, then
        ``serve/harvest`` — the walk of the fetched block over the
        dispatch's own riders, finishes, counters, the gauges."""
        self._first_tokens()
        stats = {} if flight is None else {"cycle": flight.cycle}
        with self._phase("harvest", **stats):
            if flight is not None:
                self._walk(flight, *fetched)
            self._harvest_numerics()
            self._observe_gauges()

    def _walk(self, flight: _Flight, fetched, firsts) -> None:
        persistent, spec = self._persistent, self.speculate
        read = _DECODE_VARIANTS[persistent, bool(spec)][2]
        tokens, count, n_it = read(*fetched)
        self._pending_first.clear()  # the persistent loop's: ``firsts``
        self.metrics.count("decode_dispatches")
        self.metrics.count("decode_steps", n_it)
        self._record_tp_collectives(self.num_slots * (spec + 1), n_it)
        if persistent:
            self.metrics.count("ring_drains")
            self.metrics.count("loop_iterations", n_it)
            self.metrics.observe_ring(n_it)
        now = time.monotonic()
        # streamed tail (opt-in): the iteration-0 callback timestamp is
        # when the wave's first tokens actually existed host-side —
        # tighter than the drain time for first-token latency
        first_ts = now
        if self._stream_events:
            first_ts = min(now, self._stream_events[0][0])
        emitted = 0
        any_cut = False
        for req, slot in flight.riders:
            if req.finish_reason is not None:
                # finished since the dispatch: by a deadline (the token
                # is dropped with the rest), or by the walk before this
                # one, a dispatch late — the device had frozen the slot
                # and this block holds its last token again, which is
                # NOT the next tenant's (``lagged_slot_steps``)
                continue
            taken = 0
            finished = False
            if slot in firsts:
                tok = int(firsts[slot])
                self._record_first(req, tok, first_ts)
                # finished here, the device's fin0 froze this slot
                # before iteration 0 (EOS first token / one-token
                # budget): it idled the whole loop
                finished = self._check_finished(req, tok, first_ts)
            if not finished:
                for j in range(n_it):
                    c = count.item(j, slot)
                    if c == 0:
                        break  # frozen from here on: rows are rewrites
                    if not taken:  # this block holds tokens of the request
                        if req.first_decode_cycle is None:
                            req.first_decode_cycle = flight.cycle
                        req.last_decode_cycle = flight.cycle
                    taken = j + 1
                    if spec:
                        # per live slot-iteration: spec lanes drafted,
                        # c - 1 of them accepted, the rest of the
                        # spec + 1 verify lanes spent on rejected
                        # (overwritten-before-visible) positions
                        self.metrics.count("draft_tokens_proposed", spec)
                        self.metrics.count("draft_tokens_accepted", c - 1)
                        self.metrics.count(
                            "spec_rejected_lane_steps", (spec + 1) - c
                        )
                    # the iteration's block: one lane for the one-token
                    # programs, the c accepted lanes of a speculative
                    # one.  The device truncation rule puts any finish
                    # on the block's LAST emitted token
                    # (``generation._make_spec_decode_body``), so walk
                    # and frozen carry agree token for token.  Scalars
                    # are read straight out of the fetched arrays: this
                    # runs per slot and step (``serve.harvest_ms_p50``)
                    for i in range(c):
                        tok = tokens.item(j, slot, i)
                        self._ntok[slot] += 1
                        self.cache.advance_slot(slot)
                        self._last_tok[slot] = tok
                        if spec:
                            # post-advance, the slot's position IS the
                            # token's stream index: the draft history's
                            # row for it
                            p = int(self.cache.pos[slot])
                            if p < self.max_len:
                                self._hist[slot, p] = tok
                        req.generated.append(tok)
                        emitted += 1
                        if self._check_finished(req, tok, now):
                            finished = True
                            break
                    if finished:
                        break
            if finished:
                # the device froze this slot for the rest of the chunk
                # (or the loop ran on past it): those slot-iterations
                # bought nothing
                self.metrics.count("masked_slot_steps", n_it - taken)
            else:
                any_cut = True  # this dispatch ended before the request
        if persistent and any_cut:
            self.metrics.count("ring_full_drains")
        self.metrics.count("tokens_generated", emitted)
        self.metrics.count("tokens_decoded", emitted)
        self._record_drain()

    def _check_finished(self, req: Request, tok: int, now: float) -> bool:
        if self.eos_token is not None and tok == self.eos_token:
            self._finish(req, "stop", now)
        elif len(req.generated) >= req.max_new_tokens:
            self._finish(req, "length", now)
        elif self.cache.full(req.slot):
            # no row left for another token; submit-time validation makes
            # this unreachable today, but the geometry guard stays
            self._finish(req, "cache_full", now)
        else:
            return False
        return True

    def _finish(self, req: Request, reason: str, now: float) -> None:
        slot = req.slot
        pending = self._pending_first.pop(slot, None)
        if pending is not None:
            # rare pre-drain exit (deadline expiry between prefill and
            # the first drain): the prefill DID sample a token — flush
            # it so the truncated result matches what the chunked
            # engine would have returned, at the cost of one sync
            tok = int(np.asarray(pending))
            self.metrics.count("host_syncs")
            self._harvest_numerics()
            self._record_first(req, tok, now)
            held = self._prefill_host_s.pop(slot, None)
            if held is not None:
                self.metrics.prefill_s.record(held)
        flight = self._in_flight
        if flight is not None and reason != "deadline":
            # one of the device's own rules, seen a dispatch late: the
            # dispatch in flight carries the slot frozen (a deadline is
            # the host's: that slot's token is computed and dropped)
            self.metrics.count("lagged_slot_steps", flight.iterations)
            self.metrics.count("masked_slot_steps", flight.iterations)
        self._source[slot] = FROM_HOST  # the next dispatch: finished
        self.scheduler.retire(req)
        self.cache.retire(slot)  # paged: also rewires the table to scratch
        if self.paged and req.pages is not None:
            # drop the request's references; pages the prefix index
            # adopted live on under its own refcount until LRU eviction,
            # the rest return to the free pool
            self.pool.decref(req.pages)
            req.pages = None
        self._temps[slot] = 0.0
        req.finish_reason = reason
        req.finished_at = now
        req.record_event(
            "finish", ts=now, reason=reason,
            first_cycle=req.first_decode_cycle,
            last_cycle=req.last_decode_cycle,
        )
        self._count_finish(req)

    def _count_finish(self, req: Request) -> None:
        self.metrics.count("requests_completed")
        result = req.result()
        if result.truncated:
            self.metrics.count("requests_truncated")
        # derived per-request latencies feed the aggregates (same
        # timestamps as RequestResult / the per-request trace track)
        self.metrics.e2e_latency_s.record(result.latency_s)
        if result.tpot_s is not None:
            self.metrics.tpot_s.record(result.tpot_s)
        self._finished.append(req)
        if self._bb_on:
            # retired from scheduler.running before the walk's drain
            # fold — park it so the fold still sees its final tokens
            self._bb_finished_pending.append(req)
