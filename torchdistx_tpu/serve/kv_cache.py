"""Slot- and page-based, fixed-geometry KV caches for continuous batching.

Two device layouts behind one host-bookkeeping contract:

- :class:`SlotKVCache` — per layer ``(k, v)`` arrays of shape
  ``(num_slots, max_len, heads * head_dim)``.  HBM cost is
  ``num_slots x max_len`` regardless of actual request lengths.
- :class:`PagedKVCache` — per layer ``(k, v)`` **page pools** of shape
  ``(num_pages, page_size, heads * head_dim)``, plus host-side per-slot
  page tables padded to ``max_len / page_size`` entries.  A slot's
  logical cache is the concatenation of the pages its table row names;
  requests claim only the pages their ``prompt + max_new_tokens``
  footprint needs, and page-aligned shared prefixes are handed over by
  **table rewrite** (two tables naming the same page), never by copying
  KV.

**The stored layout is the decode kernel's operand layout**: the head
tail is merged, ``(lead, rows, Hkv * D)`` (int8 scales ``(lead, rows,
Hkv)``), because that is the array ``ops/decode_attention.py`` hands to
Mosaic, and on the chip a ``(…, Hkv, D)`` array and its ``(…, Hkv * D)``
"view" are tiled differently: the reshape between them copies the whole
array (that file's docstring has the two tilings).  The merge happens
once, at construction (:func:`merge_heads` on what
``model.init_cache(lead, rows)`` returns); models keep their
``(B, S, Hkv, D)`` ``init_cache`` / ``forward_cached`` contract for
``generate()`` and for the prefill's one-request slab.  Every write
flattens the NEW ROWS, never the cache; a reader that needs heads (the
jnp attends, :func:`paged_view`, chunked prefill's warm program) takes
a 4-D view of what it read (:func:`split_heads`).

**A layer's entry says what kind it is** (:func:`entry_kind`), and a
cache may hold layers of different kinds:

- a **pair** ``(k, v)``, a plain tuple, the models' documented cache
  contract -- and, under ``kv_dtype="int8"``, the cache's own quantized
  representation of it, ``(k, v, k_scale, v_scale)``;
- a **latent** entry, :class:`LatentEntry` (multi-head latent attention,
  ``models/deepseek_v3``): ONE array ``(num_slots, max_len, W)`` -- a
  token's compressed key/value and its one shared rope key side by side
  (``kv_lora_rank + qk_rope_head_dim`` = 576 lanes, which the model
  zero-pads to whole 128-lane tiles, ``W`` = 640), where the pair
  layout would hold ``heads x (qk + v)`` (10240 for the same model).  It
  has no head axis to merge: ``model.init_cache`` already returns it in
  the form ``tdx_latent_decode_attention`` reads, prefill writes its
  slab with the same ``dynamic_update_slice`` and a decode step its row
  with the same scatter as a pair's arrays;
- a **recurrent state**, :class:`RecurrentState` (a state-space layer,
  ``models/jamba``; a Gated-DeltaNet layer, ``models/qwen3_next``):
  ``conv (num_slots, (K - 1) * width)``, the last ``K - 1`` inputs of
  the layer's causal convolution, and ``ssm`` float32, the recurrence's
  state in the model's layout (``(num_slots, d_state, d_inner)`` for
  Mamba-1, ``(num_slots, heads, Dk, Dv)`` for the delta rule's matrix a
  head) -- what a slot holds of the whole context, constant in its
  length.  It
  has NO row axis and no position: a prefill writes the slot's state
  whole (``write_slot``: the state after the prompt's last REAL token;
  the model sees to that, ``models/jamba.py``) and every decode step
  rewrites the state of EVERY slot whole (in place:
  ``tdx_selective_state_update``, ``tdx_gated_delta_update``), that of a
  retired or frozen slot
  too.  That is safe by the argument this module makes for rows,
  overwrite-before-visible: nothing reads a slot's state but that
  slot's own next step, and a slot is read again only after an
  admission, whose prefill has overwritten the state whole.  The
  model's layout and dtypes are kept as they are (no head tail to
  merge; ``kv_dtype`` casts rows, never the float32 state).

Only the slab layout holds a latent entry or a recurrent state (the
engine refuses paging, int8 and the warm / chunked programs over them,
by name); the kinds are told by the entry's TYPE, layer by layer, never
by its length or by layer 0.

In both, admitting/retiring a request changes only tiny dynamic inputs
(positions, a table row, a host bit) — never a device shape — so the
compiled prefill/decode programs survive any admit/retire sequence: the
property the whole engine is built on.

The same invariance is what lets the persistent decode loop
(``decode_mode="persistent"``) freeze a finished slot ON DEVICE for an
arbitrary number of while-loop iterations: the host only frees pages,
rewrites table rows, or flips ``active`` bits at drain boundaries
(between loop dispatches), so within any one dispatch the table input
is loop-invariant — a frozen slot's in-loop rewrites land in pages its
table owned when the loop launched, or (once retired at a previous
drain) on the scratch page, never on a page reallocated mid-loop.

Stale-row safety (paged): a freed page's old K/V rows are NOT zeroed.
They are unreachable by construction — a page is freed only when its
refcount reaches zero, i.e. no live page table references it (retiring a
slot rewires its whole table row to the reserved scratch page, so even
the frozen post-finish decode writes of a fused chunk land harmlessly in
scratch) and the prefix index no longer holds it; while the index DOES
hold a page, its refcount keeps it out of the free list, so an allocated
page can never be reached through some other request's stale table.
Within a live slot the slab-era argument still applies row-wise: a query
attends view rows ``j <= pos`` only, prefill overwrites the suffix rows
it claims, and each decode step overwrites row ``pos`` before ``pos``
advances to make it visible — every *visible* row of every *referenced*
page was written by a request entitled to it (the owning request, or the
request that computed the shared prefix).  Garbage beyond — bucket
padding, scratch-page scribbles, stale rows of reused pages — is masked
to exactly-zero probability and never perturbs a stream (regression:
``tests/test_prefix_cache.py`` reuses a retired request's pages and pins
bit-identity against a fresh engine).

Variable advance (speculative decode): with ``ServeEngine(speculate=K)``
each verify call writes K+1 rows ``pos .. pos + K`` per slot
(:func:`scatter_slot_tokens` / :func:`paged_scatter_tokens`) but ``pos``
advances only by the TRACED accepted count ``e``.  The row-wise argument
extends: rows ``pos .. pos + e - 1`` hold K/V of exactly the accepted
token stream; rejected-lane rows ``pos + e .. pos + K`` sit beyond the
new depth and are rewritten by the next verify before the visibility
mask reaches them — overwrite-before-visible, the same invariant as the
frozen-slot rewrites.  Rows that would land past ``max_len`` are DROPPED
by the scatter (OOB index + ``mode="drop"``), never clamped: a clamped
write would corrupt the slot's last row, and an unclamped flat index
would alias into the NEXT slot's row 0 (slab) or an arbitrary pool row
(paged).  In the paged layout the rejected/frozen overflow beyond a
slot's allocated chain routes through its table to the scratch page,
exactly like the frozen single-token writes.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.numerics import tap, tap_error
from .prefix_cache import SCRATCH_PAGE

__all__ = [
    "SlotKVCache",
    "PagedKVCache",
    "merge_heads",
    "split_heads",
    "heads_view",
    "stored_rows",
    "LatentEntry",
    "RecurrentState",
    "entry_kind",
    "cache_kinds",
    "write_slot",
    "paged_view",
    "paged_scatter_rows",
    "scatter_slot_tokens",
    "paged_scatter_tokens",
    "quantize_kv",
    "dequantize_kv",
    "quantize_cache",
    "dequantize_cache",
    "canonicalize_kv_dtype",
]

# -- int8 KV quantization ---------------------------------------------------
#
# ``kv_dtype="int8"`` stores each layer as a 4-tuple ``(k, v, k_scale,
# v_scale)`` instead of the ``(k, v)`` pair: int8 data plus f32
# per-token-row per-head scales, ``(…, Hkv, 1)`` as :func:`quantize_kv`
# makes them and ``(lead, rows, Hkv)`` as the engine stores them.  The
# scales are DEVICE arrays riding through the same scatter/gather sites
# as the data (they share its leading dims, so every (lead, row) index
# computed for a K/V write addresses the matching scale row) — host-side
# scales could not ride through the donated jitted programs.
#
# Scales are constrained to POWERS OF TWO (``s = 2^ceil(log2(amax/127))``
# via frexp/ldexp).  That makes ``dequantize(quantize(x))`` exactly
# idempotent at the value level: requantizing a dequantized row yields
# ``s' = s * 2^c``, ``q' = q * 2^-c`` with both steps exact in f32, so
# ``q' * s' == q * s`` bit for bit.  The warm-prefill program and the
# paged prefill both round-trip untouched prefix rows through
# dequantize → forward → requantize, and this property is what keeps
# those rows bit-stable across the trip (the same contract the f32
# cache gets for free).

_KV_DTYPES = {
    "int8": jnp.int8,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float32": jnp.float32,
}


def canonicalize_kv_dtype(kv_dtype: Any) -> Optional[str]:
    """``None`` → model-default cache dtype; otherwise a canonical dtype
    name from the supported set (``int8`` quantized; ``bfloat16`` /
    ``float16`` / ``float32`` plain casts, e.g. a bf16 A/B baseline for
    an f32 model)."""
    if kv_dtype is None:
        return None
    name = str(np.dtype(kv_dtype).name) if not isinstance(
        kv_dtype, str
    ) else kv_dtype
    if name not in _KV_DTYPES:
        raise ValueError(
            f"kv_dtype must be one of {sorted(_KV_DTYPES)} or None "
            f"(model default), got {kv_dtype!r}"
        )
    return name


def quantize_kv(x: jax.Array):
    """Quantize K or V rows to ``(int8 data, f32 power-of-two scales)``.

    ``x``: (..., H, D).  Returns ``q`` of ``x.shape`` int8 and ``scale``
    of ``x.shape[:-1] + (1,)`` f32 with ``scale = 2^ceil(log2(amax/127))``
    per (row, head) — the smallest power of two whose 127-step grid
    covers the row (all-zero rows get a harmless 0.5).  Values quantize
    as ``round(x / scale)`` clipped to [-127, 127]; dequantization is
    ``q * scale`` (exact: int8 times power of two)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    m, e = jnp.frexp(amax / jnp.float32(127.0))
    # frexp: v = m * 2^e, m in [0.5, 1) — ceil(log2 v) is e except at
    # exact powers of two (m == 0.5), where it is e - 1
    scale = jnp.ldexp(
        jnp.ones_like(m), e - (m <= jnp.float32(0.5)).astype(e.dtype)
    )
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Exact inverse read of :func:`quantize_kv`: f32 ``q * scale``."""
    return q.astype(jnp.float32) * scale


def _tap_quant(orig: jax.Array, q: jax.Array, scale: jax.Array) -> None:
    """Numerics-observatory probe at a quantize-on-write site: digest of
    the dequantization error ``orig - q*scale`` plus the scale rows
    themselves (``max_abs`` of the scale digest is the ``s`` in the
    round-to-nearest bound ``|err| <= s/2``).  Identity without an
    active tape — the default serve programs trace byte-identically."""
    tap_error("kv_quant_err", orig, dequantize_kv(q, scale))
    tap("kv_quant_scale", scale)


def quantize_cache(kv: Any) -> Any:
    """Pairs → per-layer ``(k, v, k_scale, v_scale)`` 4-tuples (the
    model's ``(…, Hkv, D)`` layout on both sides)."""
    out: List[tuple] = []
    for k, v in kv:
        qk, sk = quantize_kv(k)
        qv, sv = quantize_kv(v)
        out.append((qk, qv, sk, sv))
    return out


def dequantize_cache(kv: Any) -> Any:
    """4-tuples (or pass-through pairs) → f32 ``(k, v)`` pairs."""
    out: List[tuple] = []
    for entry in kv:
        if len(entry) == 4:
            k, v, sk, sv = entry
            out.append((dequantize_kv(k, sk), dequantize_kv(v, sv)))
        else:
            out.append(entry)
    return out


# -- the stored layout ------------------------------------------------------


def merge_heads(x: jax.Array) -> jax.Array:
    """Model layout → stored layout: K/V ``(…, Hkv, D)`` →
    ``(…, Hkv * D)``, scales ``(…, Hkv, 1)`` → ``(…, Hkv)``.  Applied
    to the whole cache once, at construction, and to NEW ROWS at every
    write."""
    return x.reshape(*x.shape[:-2], -1)


def split_heads(x: jax.Array, kv_heads: int) -> jax.Array:
    """Stored layout → model layout: the inverse of :func:`merge_heads`
    for a reader that needs the head axis (K/V ``(…, Hkv * D)`` →
    ``(…, Hkv, D)``, scales ``(…, Hkv)`` → ``(…, Hkv, 1)``).  Only ever
    applied to what was READ for a jnp path, never on the kernel path:
    on the chip it is a copy of its operand."""
    return x.reshape(*x.shape[:-1], kv_heads, -1)


def heads_view(entry: Any, kv_heads: int) -> tuple:
    """Stored ``(k, v)`` / ``(k, v, k_scale, v_scale)`` arrays (or any
    rows gathered from them) → the model-facing ``(k, v)`` pair with
    the head axis back, dequantized where there are scales."""
    k, v = (split_heads(a, kv_heads) for a in entry[:2])
    if len(entry) == 4:
        ks, vs = (split_heads(a, kv_heads) for a in entry[2:])
        return dequantize_kv(k, ks), dequantize_kv(v, vs)
    return k, v


def stored_rows(entry: Any, k: jax.Array, v: jax.Array) -> tuple:
    """Freshly computed K/V rows ``(…, Hkv, D)`` → one array per array
    of the stored ``entry``, each in that array's dtype with the head
    tail merged: ``(k, v)``, or quantized on the way ``(k, v, k_scale,
    v_scale)`` — so a write site is one ``zip`` over the entry whatever
    the cache's dtype."""
    if len(entry) == 4:
        qk, sk = quantize_kv(k)
        qv, sv = quantize_kv(v)
        _tap_quant(k, qk, sk)
        _tap_quant(v, qv, sv)
        rows = (qk, qv, sk, sv)
    else:
        rows = (k, v)
    return tuple(merge_heads(x).astype(c.dtype) for x, c in zip(rows, entry))


class LatentEntry(NamedTuple):
    """A layer's latent cache (module docstring): one array ``(lead,
    rows, W)``."""

    latent: jax.Array


class RecurrentState(NamedTuple):
    """A recurrent layer's per-slot state (module docstring): ``conv
    (lead, (K - 1) * width)`` and ``ssm`` float32 in the model's layout,
    ``(lead, d_state, d_inner)`` or ``(lead, heads, Dk, Dv)``."""

    conv: jax.Array
    ssm: jax.Array


PAIR, LATENT, STATE = "pair", "latent", "state"


def entry_kind(entry: Any) -> str:
    """What a layer's cache entry holds: ``"state"``
    (:class:`RecurrentState`), ``"latent"`` (:class:`LatentEntry`) or
    ``"pair"`` -- the plain tuple of the models' ``(k, v)`` contract,
    which the cache itself may hold quantized (``(k, v, k_scale,
    v_scale)``).  Told by the entry's type, never by its length."""
    if isinstance(entry, RecurrentState):
        return STATE
    if isinstance(entry, LatentEntry):
        return LATENT
    return PAIR


def cache_kinds(model: Any) -> tuple:
    """The kind of every layer's entry of ``model.init_cache``, from
    shapes alone (nothing is allocated)."""
    return tuple(
        entry_kind(e) for e in jax.eval_shape(lambda: model.init_cache(1, 2))
    )


def _like(entry: Any, arrays) -> Any:
    """``arrays`` as an entry of ``entry``'s kind."""
    return tuple(arrays) if entry_kind(entry) == PAIR else type(entry)(*arrays)


def write_slot(kv: Any, slab: Any, slot) -> Any:
    """Write one request's prefilled cache slab into slot row ``slot``.

    ``kv``: the engine cache — list per layer of ``(k, v)`` with shape
    (num_slots, max_len, Hkv * D), or quantized 4-tuples ``(k, v,
    k_scale, v_scale)`` with scales (num_slots, max_len, Hkv) (the slab
    pairs quantize on write).  ``slab``: ``init_cache(1, bucket)``
    output run through the model's prefill — list per layer of
    ``(k, v)`` with shape (1, bucket, Hkv, D), the model's layout; its
    head tail is merged here, on the slab (2 MB an array at bucket
    1024), not on the cache.  ``slot`` may be traced (it is, inside the
    jitted prefill program); the write is a pure
    ``dynamic_update_slice`` per layer — no recompile across slots.
    A latent entry's slab ``(latent (1, bucket, W),)`` is already in the
    stored form, and so is a recurrent state's ``(conv (1, .), ssm (1,
    ., .))``, which replaces the slot's state whole.
    """
    return [
        _like(
            entry,
            (
                lax.dynamic_update_slice(
                    c, x.astype(c.dtype), (slot,) + (0,) * (c.ndim - 1)
                )
                for c, x in zip(
                    entry,
                    stored_rows(entry, *s) if entry_kind(entry) == PAIR else s,
                )
            ),
        )
        for entry, s in zip(kv, slab)
    ]


def paged_view(kv: Any, tables: jax.Array, kv_heads: int) -> Any:
    """Gather slots' logical caches from the page pools.

    ``kv``: list per layer of ``(k, v)`` pools, shape (num_pages,
    page_size, Hkv * D).  ``tables``: one slot's (pages_per_slot,) int32
    page ids, or (B, pages_per_slot) for B slots (unassigned entries
    name the scratch page — their rows are garbage but sit beyond the
    visibility mask).  Returns the model-facing view: list per layer of
    ``(k, v)`` with shape (B, max_len, Hkv, D) — B = 1 for one table
    row — where ``max_len = pages_per_slot * page_size`` and
    ``Hkv = kv_heads``.  A pure gather — the pools are read, never
    copied page-to-page.  Quantized 4-tuple pools dequantize in the
    gather: the view is always model-dtype pairs.
    """
    tables = jnp.atleast_2d(tables)
    return [
        heads_view(
            [
                a[tables].reshape(tables.shape[0], -1, a.shape[-1])
                for a in entry
            ],
            kv_heads,
        )
        for entry in kv
    ]


def paged_scatter_rows(
    kv: Any, view: Any, table_row: jax.Array, page_size: int, start, length: int
) -> Any:
    """Write ``length`` freshly computed rows of an updated slot view
    (``(1, max_len, Hkv, D)`` pairs, starting at traced row ``start``)
    back into the page pools through the slot's table row.  Only the
    suffix span moves — shared prefix pages are never rewritten.
    ``length`` is static (the prefill bucket); rows landing past the
    slot's allocated pages route to the scratch page (bucket padding)
    and are never visible.  Quantized 4-tuple pools quantize the suffix
    on write (the scale rows scatter through the same (page, row)
    indices as the data)."""
    offs = start + jnp.arange(length)
    pages, rows = table_row[offs // page_size], offs % page_size
    out: List[tuple] = []
    for entry, (wk, wv) in zip(kv, view):
        seg_k = lax.dynamic_slice_in_dim(wk[0], start, length, axis=0)
        seg_v = lax.dynamic_slice_in_dim(wv[0], start, length, axis=0)
        out.append(
            tuple(
                c.at[pages, rows].set(x)
                for c, x in zip(entry, stored_rows(entry, seg_k, seg_v))
            )
        )
    return out


def scatter_slot_tokens(
    cache: jax.Array, x_new: jax.Array, positions: jax.Array
) -> jax.Array:
    """Write ``S`` consecutive freshly computed rows per slot into the
    contiguous slab at each slot's own depth: the decode step's write
    (``S == 1``) and the multi-token one (``ServeEngine(speculate=K)``
    verifies ``S = K + 1`` candidate positions per iteration).

    ``cache``: (num_slots, max_len, T) with ``T = Hkv * D`` (scales:
    ``Hkv``).  ``x_new``: (B, S, T), or the rows with their head axis
    still split, (B, S, Hkv, D): the tail is merged here, on the rows.
    ``positions``: (B,) int32 — slot ``b``'s rows land at
    ``positions[b] + [0..S)``.  Rows past ``max_len`` are DROPPED — the
    scatter indexes (slot, row) pairs, a row index past the slab is out
    of bounds and ``mode="drop"`` discards it: NOT clamped (a clamped
    write would corrupt row ``max_len - 1``) and with nowhere to wrap
    to (a flat ``b * max_len + row`` index past the slot would alias
    into slot ``b + 1``'s row 0).

    ONE scatter, not a ``vmap`` of ``dynamic_update_slice``: XLA's TPU
    compiler runs this as one fusion an array, and expands the vmapped
    form into a loop over the slots of five small operations each —
    0.62 s of a traced 4 s at 16 slots x 48 arrays a step, where a row
    of the stored layout is a sixteenth of a tile (PERF.md §6, PR 28).
    """
    b, s = x_new.shape[0], x_new.shape[1]
    rows = positions[:, None] + jnp.arange(s)[None, :]
    slots = jnp.broadcast_to(jnp.arange(b)[:, None], rows.shape)
    return cache.at[slots, rows].set(
        x_new.astype(cache.dtype).reshape(b, s, cache.shape[-1]),
        mode="drop",
    )


def paged_scatter_tokens(
    pool: jax.Array,
    x_new: jax.Array,
    page_tables: jax.Array,
    positions: jax.Array,
    page_size: int,
) -> jax.Array:
    """Paged sibling of :func:`scatter_slot_tokens`: route each of the
    ``S`` per-slot rows through the slot's page table into the page
    pool.

    ``pool``: (num_pages, page_size, T).  ``x_new``: (B, S, T) or
    (B, S, Hkv, D), as in :func:`scatter_slot_tokens`.
    ``page_tables``: (B, pages_per_slot) int32.  ``positions``: (B,).
    Logical rows past ``max_len`` are dropped (page index out of bounds
    + ``mode="drop"``); rows inside ``max_len`` but past the slot's
    allocated chain follow the table to the scratch page, exactly like
    the frozen single-token writes (module docstring).
    """
    npages = pool.shape[0]
    b, s = x_new.shape[0], x_new.shape[1]
    pp = page_tables.shape[1]
    offs = positions[:, None] + jnp.arange(s)[None, :]
    page = jnp.take_along_axis(
        page_tables, jnp.clip(offs // page_size, 0, pp - 1), axis=1
    )
    page = jnp.where(
        offs < pp * page_size, page, npages  # out of bounds on purpose
    )
    return pool.at[page, offs % page_size].set(
        x_new.astype(pool.dtype).reshape(b, s, pool.shape[-1]),
        mode="drop",
    )


class _HostBookkeeping:
    """The pos/active arrays both cache layouts share.

    ``pos[slot]`` is the number of tokens currently cached for the slot
    (equivalently: the row the slot's NEXT token will be written to);
    ``active[slot]`` marks slots owned by a running request.  Both live
    as host numpy — they ride into the compiled programs as tiny dynamic
    inputs, never as static values.
    """

    num_slots: int
    max_len: int

    def _init_host(self, num_slots: int, max_len: int) -> None:
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.pos = np.zeros(self.num_slots, np.int32)
        self.active = np.zeros(self.num_slots, bool)

    def admit(self, slot: int, true_len: int) -> None:
        """Claim ``slot`` for a freshly prefilled request of ``true_len``
        prompt tokens (the engine's prefill program writes the KV)."""
        if self.active[slot]:
            raise ValueError(f"slot {slot} is already active")
        if not 0 < true_len <= self.max_len:
            raise ValueError(
                f"prompt length {true_len} outside (0, {self.max_len}]"
            )
        self.pos[slot] = true_len
        self.active[slot] = True

    def advance_slot(self, slot: int) -> None:
        """One slot cached one more token.  Advancement is per-slot (not
        an all-active-slots sweep) because the engine's fused-chunk walk
        consumes a different number of the chunk's K steps per request —
        a finished slot must stay exactly where the device froze it."""
        self.pos[slot] += 1

    def retire(self, slot: int) -> None:
        self.active[slot] = False

    def full(self, slot: int) -> bool:
        """No room to decode another token into this slot."""
        return int(self.pos[slot]) >= self.max_len

    def positions(self) -> np.ndarray:
        """Per-slot write positions for the decode program, clamped into
        range for inactive slots (their rows are dead weight either way —
        see the stale-row note in the module docstring)."""
        return np.clip(self.pos, 0, self.max_len - 1).astype(np.int32)

    @property
    def active_count(self) -> int:
        return int(self.active.sum())

    @property
    def nbytes(self) -> int:
        return sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for pair in self.kv
            for a in pair
        )

    def _row_entries(self) -> list:
        """The entries that hold rows (a pair, a latent entry): a
        recurrent state has none."""
        return [e for e, k in zip(self.kv, self.kinds) if k != STATE]

    @property
    def latent(self) -> bool:
        """Some layer's entry is a latent array."""
        return LATENT in self.kinds

    @property
    def kv_data_nbytes(self) -> int:
        """Bytes of the K/V data arrays alone (scales and recurrent
        state excluded) — the quantity that halves exactly under
        ``kv_dtype="int8"``."""
        return sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for entry in self._row_entries()
            for a in entry[:2]
        )

    @property
    def kv_row_bytes(self) -> int:
        """Bytes one token takes in the data arrays of ONE layer that
        holds rows: ``2 x Hkv x D x itemsize`` for a pair, ``W x
        itemsize`` for a latent entry (1280 for DeepSeek-V3's 576 bf16
        lanes stored on 640).  The first such layer is read (a model's
        row layers are alike); 0 where no layer holds rows."""
        rows = self._row_entries()
        if not rows:
            return 0
        return sum(a.shape[-1] * a.dtype.itemsize for a in rows[0][:2])

    @property
    def kv_scale_nbytes(self) -> int:
        """Bytes of the f32 scale arrays (0 for unquantized caches)."""
        return sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for entry in self._row_entries()
            for a in entry[2:]
        )

    @property
    def state_slot_bytes(self) -> int:
        """Bytes ONE slot holds of recurrent state, all layers together
        (0 without such a layer): constant in the context length, and
        what a decode step reads and writes once a slot."""
        return sum(
            int(np.prod(a.shape[1:])) * a.dtype.itemsize
            for entry, kind in zip(self.kv, self.kinds)
            if kind == STATE
            for a in entry
        )

    @property
    def kv_dtype_name(self) -> str:
        """The dtype the rows are stored in (the first layer that holds
        rows; a cache of recurrent state alone names its first array)."""
        rows = self._row_entries() or self.kv
        return str(rows[0][0].dtype)

    def _init_device(
        self, model: Any, lead: int, rows: int, kv_dtype: Any, placement: Any
    ) -> None:
        """Build ``self.kv``: ``model.init_cache(lead, rows)`` — the
        model's layout and dtype, ``(lead, rows, Hkv, D)`` pairs —
        brought into the stored representation (the cache's dtype, every
        array's head tail merged: module docstring; a latent entry and a
        recurrent state are stored as the model makes them) and
        COMMITTED to ``placement``.  Also records ``kinds`` (every
        layer's :func:`entry_kind`), ``kv_dtype`` / ``quantized`` /
        ``kv_heads`` (the readers that need the head axis back ask for
        the last).

        One jitted program makes the stored arrays directly: built
        eagerly, the model-layout cache and its merged (or quantized)
        copy would both be alive for a moment — twice the cache, which a
        7B model's weights leave no room for on one chip.  The program
        lives in the model's jit store (``generation._cached_jit``),
        keyed by geometry, dtype and placement, so a second engine on
        the same model (a warmed scale-up, a fleet replica) compiles
        nothing.

        Why committed: the engine's programs return committed arrays,
        and an uncommitted first-call cache would flip the jit signature
        (committed-ness is part of it) on the second call — one silent
        recompile per program, the exact class the two-program
        discipline exists to prevent.  The placement must agree with the
        params' devices (mixed committed device sets are a jit error),
        so the engine derives it from the params (replicated over their
        mesh when they are sharded).  Under ServeEngine(mesh=) it is a
        NamedSharding that shards the merged Hkv * D axis over tp —
        contiguous Hkv/tp head groups, so each device commits only its
        head slice; the f32 scale arrays of a quantized cache share the
        data's leading dims with an Hkv tail, so the same NamedSharding
        commits them alongside their head slice.  Everything host-side
        (lengths, active, page tables) is per-slot metadata and never
        sharded."""
        from jax.sharding import Sharding, SingleDeviceSharding

        from ..generation import _cached_jit

        self.kv_dtype = kv_dtype = canonicalize_kv_dtype(kv_dtype)
        self.quantized = quantized = kv_dtype == "int8"
        shapes = jax.eval_shape(lambda: model.init_cache(lead, rows))
        self.kinds = kinds = tuple(entry_kind(e) for e in shapes)
        if quantized and any(k != PAIR for k in kinds):
            raise ValueError(
                "kv_dtype='int8' is not supported over a latent cache or "
                "recurrent state: the per-head scales have no head to "
                "belong to"
            )
        dt = None if kv_dtype is None else _KV_DTYPES[kv_dtype]

        def stored():  # closes over the model only, never over ``self``
            out = []
            for kind, entry in zip(kinds, model.init_cache(lead, rows)):
                if kind == STATE:  # the model's layout and dtypes, as is
                    out.append(entry)
                elif kind == LATENT:  # no head tail to merge
                    (c,) = entry
                    out.append(LatentEntry(c if dt is None else c.astype(dt)))
                else:
                    if quantized:
                        (entry,) = quantize_cache([entry])
                    elif dt is not None:
                        entry = tuple(a.astype(dt) for a in entry)
                    out.append(tuple(merge_heads(a) for a in entry))
            return out

        # the head count of the layers that have heads (a latent row is
        # shared by every head; a recurrent state has none): what the
        # readers that need the head axis back ask for
        heads = {int(e[0].shape[2]) for e, k in zip(shapes, kinds) if k == PAIR}
        if len(heads) > 1:
            raise ValueError(
                f"the model's pair layers disagree on their KV heads: {heads}"
            )
        self.kv_heads = heads.pop() if heads else None
        if placement is None:
            placement = jax.devices()[0]
        sharding = (
            placement
            if isinstance(placement, Sharding)
            else SingleDeviceSharding(placement)
        )
        make = _cached_jit(
            model, "_kv_init_jit_cache", (lead, rows, kv_dtype, sharding),
            stored, out_shardings=sharding,
        )
        self.kv = jax.device_put(make(), placement)


class SlotKVCache(_HostBookkeeping):
    """Host bookkeeping around the contiguous per-slot device cache."""

    def __init__(
        self,
        model: Any,
        num_slots: int,
        max_len: int,
        placement: Optional[Any] = None,
        kv_dtype: Optional[str] = None,
    ):
        self._init_host(num_slots, max_len)
        self._init_device(
            model, self.num_slots, self.max_len, kv_dtype, placement
        )


class PagedKVCache(_HostBookkeeping):
    """Host bookkeeping around the page-pool device cache.

    The device arrays are per-layer ``(k, v)`` pools of shape
    ``(num_pages, page_size, Hkv * D)``; ``page_tables`` maps each slot's
    logical rows onto pages (``pages_per_slot = max_len / page_size``
    int32 entries per slot, unassigned entries naming the scratch page).
    The table rides into the compiled programs as a tiny dynamic int32
    array — rewriting it (admission, prefix handoff, retirement) never
    touches a device shape.
    """

    def __init__(
        self,
        model: Any,
        num_slots: int,
        max_len: int,
        page_size: int,
        num_pages: int,
        placement: Optional[Any] = None,
        kv_dtype: Optional[str] = None,
    ):
        self._init_host(num_slots, max_len)
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if max_len % page_size != 0:
            raise ValueError(
                f"max_len {max_len} must be a multiple of page_size "
                f"{page_size}"
            )
        if num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (scratch + one usable), got "
                f"{num_pages}"
            )
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.pages_per_slot = self.max_len // self.page_size
        self._init_device(
            model, self.num_pages, self.page_size, kv_dtype, placement
        )
        self.page_tables = np.full(
            (self.num_slots, self.pages_per_slot), SCRATCH_PAGE, np.int32
        )

    def set_table(self, slot: int, pages: List[int]) -> None:
        """Point ``slot`` at its page chain (prefix-order); entries past
        the chain name the scratch page."""
        if len(pages) > self.pages_per_slot:
            raise ValueError(
                f"{len(pages)} pages exceed pages_per_slot "
                f"{self.pages_per_slot}"
            )
        self.page_tables[slot, :] = SCRATCH_PAGE
        self.page_tables[slot, : len(pages)] = pages

    def retire(self, slot: int) -> None:
        """Free the slot AND rewire its table to the scratch page: a
        fused chunk keeps rewriting a finished slot's frozen row on
        device, and after the pages are freed (and possibly reallocated)
        those writes must land somewhere no live request reads."""
        super().retire(slot)
        self.page_tables[slot, :] = SCRATCH_PAGE
