"""Autoregressive generation with a static-shape KV cache.

``generate`` drives decoder-only models exposing ``init_cache(batch,
max_seq)`` and ``forward_cached(tokens, cache, cache_pos) -> (logits,
cache)`` (Llama and GPT-2 ship both).  ``generate_encdec`` drives
encoder-decoder models exposing ``encode``, ``init_decoder_cache(enc,
max_seq)`` and ``decode_step`` (T5).  In both, the whole decode — prefill
plus a ``lax.scan`` over new tokens — runs inside one jitted, static-shape
computation, so there is one compile per call signature and the per-token
step is a single cached executable.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .nn.module import functional_call
from .obs.numerics import (
    merge_digest_trees,
    numerics_tape,
    tap,
    zero_digest,
)

__all__ = ["generate", "generate_encdec"]

#: the serve programs' declared numerics tap sites (obs.numerics).  The
#: tape inside a scan/while body must declare its sites up front so the
#: digest accumulator can ride the loop carry with a static structure;
#: these three cover everything the decode bodies can observe — the
#: sampled-position logits plus the quantized caches' per-write
#: dequantization error and scale (serve/kv_cache.py ``_tap_quant``).
_NUMERICS_SITES = ("logits", "kv_quant_err", "kv_quant_scale")


def _zero_site_digests():
    return {s: zero_digest() for s in _NUMERICS_SITES}


def _apply_top_k(logits: jax.Array, top_k: int) -> jax.Array:
    top_k = min(int(top_k), logits.shape[-1])  # clamp to vocab
    kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
    return jnp.where(logits >= kth, logits, -jnp.inf)


def _apply_top_p(logits: jax.Array, top_p: float) -> jax.Array:
    """Nucleus filtering: keep the smallest prefix of the sorted
    distribution whose mass reaches ``top_p`` (always at least top-1)."""
    sort_idx = jnp.argsort(-logits, axis=-1)
    sorted_l = jnp.take_along_axis(logits, sort_idx, axis=-1)
    probs = jax.nn.softmax(sorted_l, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p  # mass BEFORE this token is under p
    keep = keep.at[..., 0].set(True)  # the promise: at least top-1
    masked = jnp.where(keep, sorted_l, -jnp.inf)
    inv = jnp.argsort(sort_idx, axis=-1)
    return jnp.take_along_axis(masked, inv, axis=-1)


def _check_sampling_args(top_k, top_p) -> None:
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def _make_sampler(
    temperature: float,
    out_dtype,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
):
    def sample(logits_1, k):
        if temperature <= 0.0:
            return jnp.argmax(logits_1, axis=-1).astype(out_dtype)
        scaled = logits_1.astype(jnp.float32) / temperature
        if top_k is not None:
            scaled = _apply_top_k(scaled, top_k)
        if top_p is not None:
            scaled = _apply_top_p(scaled, top_p)
        return jax.random.categorical(k, scaled, axis=-1).astype(out_dtype)

    return sample


def _make_slot_sampler(
    out_dtype,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
):
    """Per-row sampler for continuous-batching decode (``serve.engine``):
    ``sample(logits, temps, seeds, steps)`` with ``logits`` (B, V) and the
    rest (B,) — rows with ``temps[b] <= 0`` take the greedy branch, the
    rest sample at their own temperature from the key
    ``fold_in(PRNGKey(seeds[b]), steps[b])``.  Keying on (request seed,
    per-request token index) makes a request's sampled stream reproducible
    no matter which slot it lands in or what else is in flight.
    Temperature/seed/step are DYNAMIC inputs (one compiled program serves
    any greedy/sampling slot mix); ``top_k``/``top_p`` reuse
    ``_make_sampler``'s filters and stay static.  A greedy row is
    bit-identical to ``_make_sampler(0.0, ...)``."""

    def sample(logits, temps, seeds, steps):
        greedy = jnp.argmax(logits, axis=-1).astype(out_dtype)
        scaled = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]
        if top_k is not None:
            scaled = _apply_top_k(scaled, top_k)
        if top_p is not None:
            scaled = _apply_top_p(scaled, top_p)
        keys = jax.vmap(
            # per-request sampling keys derive from caller-owned seeds,
            # not parameter init; the utils/rng.py counter stream is
            # host-side state and cannot run inside this traced body
            lambda s, t: jax.random.fold_in(
                jax.random.PRNGKey(s), t  # tdx-lint: disable=TDX102 -- caller-owned seed
            )
        )(seeds, steps)
        drawn = jax.vmap(jax.random.categorical)(keys, scaled).astype(
            out_dtype
        )
        return jnp.where(temps > 0.0, drawn, greedy)

    return sample


#: rows of the packed per-slot state every serve decode program takes
#: (``pack_slot_state`` / ``_unpack_slot_state``)
SLOT_STATE_ROWS = 7

#: the fused one-token program's host array has one row more: per slot,
#: where the dispatch takes that slot's state from.  The program returns
#: its final carry packed the same way, and the next dispatch starts from
#: it wherever the host has nothing newer to say (``_make_fused_decode``)
KEEP_CARRY = 0  # the previous dispatch's final carry, still on the device
FROM_HOST = 1  # this array's column: the host changed the slot since
FIRST_ON_DEVICE = 2  # the column, but the token from the prefills' vector


def pack_slot_state(
    toks, positions, temps, seeds, steps, budgets, mask, source=None
) -> np.ndarray:
    """The serve decode programs' per-slot state as ONE host array:
    ``(SLOT_STATE_ROWS, num_slots)`` int32 — last tokens, write
    positions, temperatures (their float32 BITS), sampler seeds, tokens
    sampled so far, budgets, and the program's mask (finished for the
    fused scan, active for the persistent loop) as 0/1; with ``source``
    (``KEEP_CARRY`` / ``FROM_HOST`` / ``FIRST_ON_DEVICE`` per slot) one
    row more, still one array.

    One array is one host-to-device transfer inside the dispatch call.
    Seven arrays were seven: 0.11 ms each on the chip's host through the
    call's own argument path and 0.28 ms each through ``jnp.asarray``,
    with the device idle, in every decode step (PERF.md, PR 31).  The
    result is fresh — nothing of the caller's is aliased — and
    ``_unpack_slot_state`` gives the seven back bit for bit on the
    device."""
    toks = np.asarray(toks)
    rows = SLOT_STATE_ROWS + (source is not None)
    state = np.empty((rows, toks.shape[0]), np.int32)
    if source is not None:
        state[SLOT_STATE_ROWS] = source
    state[0] = toks
    state[1] = positions
    state[2] = np.asarray(temps, np.float32).view(np.int32)
    state[3] = seeds
    state[4] = steps
    state[5] = budgets
    state[6] = mask
    return state


def _unpack_slot_state(state):
    """``pack_slot_state``'s inverse inside a traced program: ``(toks,
    positions, temps, seeds, steps, budgets, mask)`` with the dtypes the
    decode bodies take (int32; float32 temperatures by a bit cast, so
    negative, subnormal and NaN values survive; a bool mask)."""
    toks, positions, temps, seeds, steps, budgets, mask = (
        state[i] for i in range(SLOT_STATE_ROWS)
    )
    temps = jax.lax.bitcast_convert_type(temps, jnp.float32)
    return toks, positions, temps, seeds, steps, budgets, mask != 0


def _pack_carry(carry, temps, seeds, budgets):
    """A fused dispatch's final carry ``(kv, tok, pos, stp, fin)`` and
    the three per-slot inputs it does not change, as the packed state
    the next dispatch starts from: ``pack_slot_state``'s rows, on the
    device."""
    _, tok, pos, stp, fin = carry
    return jnp.stack([
        tok, pos, jax.lax.bitcast_convert_type(temps, jnp.int32), seeds,
        stp, budgets, fin.astype(jnp.int32),
    ])


def _make_decode_body(
    model: Any,
    sampler,
    *,
    eos_token: Optional[int],
    max_len: int,
):
    """The ONE single-iteration decode body both serve decode programs
    share: ``step(params, temps, seeds, budgets, extra, carry)`` runs one
    batched ``forward_decode`` + slot-sampler iteration over the carry
    ``(kv, tok, pos, stp, fin)`` and returns the updated carry.
    ``_make_fused_decode`` wraps it in a K-length ``lax.scan``;
    ``_make_persistent_decode`` wraps the SAME function in a
    ``lax.while_loop`` — sharing the body is what makes
    persistent-vs-fused bit-identity hold by construction rather than by
    parallel maintenance of two copies of the finish/freeze rules."""

    def step(params, temps, seeds, budgets, extra, carry):
        kv, tok, pos, stp, fin = carry
        logits, kv = functional_call(
            model, params, (tok[:, None], kv, pos) + extra,
            method="forward_decode",
        )
        sampled = sampler(tap("logits", logits[:, -1, :]), temps, seeds, stp)
        new_tok = jnp.where(fin, tok, sampled)
        new_stp = jnp.where(fin, stp, stp + 1)
        hit_eos = (
            sampled == eos_token
            if eos_token is not None
            else jnp.zeros_like(fin)
        )
        hit_len = new_stp >= budgets
        hit_full = pos + 1 >= max_len  # host's cache_full, pre-clamp
        new_fin = fin | hit_eos | hit_len | hit_full
        # the finishing step still advances (the host advances before
        # it checks), then the position freezes, clamped exactly like
        # SlotKVCache.positions() clamps a retired slot's
        new_pos = jnp.where(fin, pos, jnp.clip(pos + 1, 0, max_len - 1))
        return (kv, new_tok, new_pos, new_stp, new_fin)

    return step


def _make_fused_decode(
    model: Any,
    sampler,
    *,
    eos_token: Optional[int],
    max_len: int,
    decode_chunk: int,
    numerics: bool = False,
    moe_counts: bool = False,
):
    """Build the serve engine's fused K-step decode program body: a
    ``lax.scan`` of ``decode_chunk`` single-token ``forward_decode`` +
    slot-sampler iterations (``_make_decode_body``) carrying the
    (donated) KV slab, per-slot positions, last tokens, sampler step
    counters, and an on-device *finished* mask — so the engine crosses
    the host boundary once per ``K x num_slots`` tokens instead of once
    per token.

    The sampler is ``_make_slot_sampler``'s: each emitted token draws
    from ``fold_in(PRNGKey(seeds[b]), steps[b])``, the same
    root-key-plus-monotone-counter discipline as ``utils/rng.py``'s init
    stream, so a request's sampled tokens depend only on (seed, token
    index) — never on which scan step, chunk, or slot produced them.
    Fusing K steps therefore changes no sampled value.

    Finish masking: a slot finishes when it samples ``eos_token``, its
    sampled count reaches ``budgets[b]`` (the request's
    ``max_new_tokens``), or its write position hits the cache end —
    exactly the host-side ``ServeEngine._check_finished`` rules, applied
    on-device so later scan steps freeze the slot (token, position, and
    step counter held; its KV rows never advance) instead of decoding
    garbage into it.  Rows are independent, so frozen slots cannot
    perturb live ones; the host re-derives per-request finish reasons by
    walking the emitted ``(K, B)`` block with the same rules.  A frozen
    slot keeps rewriting its own frozen row — bit-identical to what K
    separate one-step dispatches do to a retired slot's row, which is
    what makes fused-vs-sequential cache states comparable.

    Returns ``run(params, kv, carry, firsts, state, *extra) -> (kv, (K,
    B) token block, carry)``.  ``state`` is ``pack_slot_state(toks,
    positions, temps, seeds, steps, budgets, finished, source)``: one
    argument, one transfer.  ``carry`` is what the previous dispatch
    returned, never fetched: the K-th step's ``(tok, pos, stp, fin)``
    with the temperatures, seeds and budgets beside them, packed as
    ``state`` is.  A slot starts from the carry where ``source`` says
    ``KEEP_CARRY`` and from ``state``'s column elsewhere (admitted,
    expired, moved: what the host changed since), its token from
    ``firsts`` -- the ``(B,)`` vector the prefill programs write their
    sampled token into -- where ``source`` says ``FIRST_ON_DEVICE``.  So
    the next dispatch needs nothing the host learns from fetching this
    one's block, and the engine reads the block one dispatch late
    (``ServeEngine._decode_step``).  A token the host has not seen may
    already end its request (EOS as the first token, a budget of one):
    the initial mask adds those rules on the device, as the persistent
    loop's does.
    ``extra`` is empty for the contiguous slot cache; the PAGED engine
    passes its page tables there — scan-invariant (a request's full
    page-aligned footprint is allocated at admission, so no chunk ever
    needs a page the table doesn't already name) and forwarded to
    ``forward_decode`` each step.

    With ``numerics=True`` (the engine's numerics observatory) each scan
    step runs under a declared-site tape and the merged
    ``{site: digest}`` dict rides the carry, returned as one extra
    trailing output — same dispatch, one more (tiny) fetched leaf.
    ``numerics=False`` traces the program without it.

    With ``moe_counts=True`` (a model whose expert layers record under
    ``nn.moe.moe_count_tape``; without ``numerics``) the K steps' rows
    and groups are summed on the device and returned as one trailing
    int32 ``[rows, groups]`` output, which the engine accumulates
    without fetching.
    """

    step = _make_decode_body(
        model, sampler, eos_token=eos_token, max_len=max_len
    )

    def run(params, kv, carry, firsts, state, *extra):
        source = state[SLOT_STATE_ROWS]
        toks, positions, temps, seeds, steps, budgets, finished = (
            _unpack_slot_state(
                jnp.where(source != KEEP_CARRY, state[:SLOT_STATE_ROWS], carry)
            )
        )
        toks = jnp.where(source == FIRST_ON_DEVICE, firsts, toks)
        finished = finished | (steps >= budgets)
        if eos_token is not None:
            finished = finished | (toks == eos_token)
        init = (kv, toks, positions, steps, finished)

        def out(last, toks_block, *more):
            return (
                last[0], toks_block,
                _pack_carry(last, temps, seeds, budgets), *more,
            )

        if moe_counts and not numerics:
            from .nn.moe import moe_count_tape, tape_totals

            def body(carry, _):
                with moe_count_tape() as tape:
                    carry = step(params, temps, seeds, budgets, extra, carry)
                return carry, (carry[1], tape_totals(tape))

            last, (toks_block, counts) = jax.lax.scan(
                body, init, None, length=decode_chunk
            )
            return out(last, toks_block, jnp.sum(counts, axis=0))
        if not numerics:
            def body(carry, _):
                carry = step(params, temps, seeds, budgets, extra, carry)
                return carry, carry[1]  # emit new_tok

            last, toks_block = jax.lax.scan(
                body, init, None, length=decode_chunk
            )
            return out(last, toks_block)

        def body(carry, _):
            inner, digs = carry
            with numerics_tape(sites=_NUMERICS_SITES) as tape:
                inner = step(params, temps, seeds, budgets, extra, inner)
            digs = merge_digest_trees(digs, tape.digests())
            return (inner, digs), inner[1]  # emit new_tok

        (last, digs), toks_block = jax.lax.scan(
            body, (init, _zero_site_digests()), None, length=decode_chunk
        )
        return out(last, toks_block, digs)

    return run


def _make_persistent_decode(
    model: Any,
    sampler,
    *,
    eos_token: Optional[int],
    max_len: int,
    ring_capacity: int,
    stream_cb=None,
    numerics: bool = False,
):
    """Build the serve engine's PERSISTENT decode program: the fused
    body (``_make_decode_body`` — the same function the K-step scan
    runs) wrapped in a ``lax.while_loop`` that keeps decoding until a
    slot-state fixpoint (every slot finished) or the output ring fills,
    whichever comes first.  One dispatch and ONE host sync (the ring
    drain) cover a whole generation instead of one per K tokens — the
    TPU analog of CUDA-graph whole-kernel capture (docs/serving.md).

    The carry holds, on top of the fused carry ``(kv, tok, pos, stp,
    fin)``, a device-resident output ring: a ``(ring_capacity,
    num_slots)`` token block, a same-shape *valid* mask (True where the
    slot was still live when the iteration sampled — the finishing
    token included, exactly the rows the host is entitled to read), and
    the write cursor ``it``.  The ring is linear per dispatch — the
    engine drains it at loop exit and re-enters with fresh state, so a
    request outliving one ring simply spans drains ("wraparound" is
    re-entry, not in-loop circular indexing, which would let an
    unfinished slot overwrite undrained tokens).

    The *initial* finished mask is computed ON DEVICE from the dynamic
    inputs — ``~active | steps >= budgets`` plus ``toks == eos_token``
    — because in persistent mode the host defers the prefill token
    fetch (no per-prefill sync): a first token that is already EOS, or
    a ``max_new_tokens=1`` budget already spent, must freeze the slot
    before iteration 0, exactly where the chunked engine's host-side
    ``_check_finished`` would have retired it at prefill time.  The
    third host rule, cache-full, must ride in through ``active``
    itself (the engine ANDs ``pos < max_len`` over the UNCLAMPED host
    positions): the ``positions`` input here is already clamped to
    ``max_len - 1`` (``SlotKVCache.positions()``), so a device-side
    ``pos >= max_len`` test could never fire.

    ``stream_cb`` (optional): called as ``stream_cb(new_tok, live, it)``
    inside the body — the io_callback/debug-callback streamed tail for
    first-token latency (``utils.compat``); the ring drain stays the
    authoritative token path whether or not the stream fires.

    Returns ``run(params, kv, state, *extra) -> (kv, ring, valid,
    iterations)`` (``state``: ``pack_slot_state`` with ``active`` as its
    mask; the engine splices deferred first tokens into its row 0 on the
    device), plus a
    trailing merged ``{site: digest}`` dict when ``numerics=True`` (the
    accumulator rides the loop carry — the drain stays the one sync).
    """

    step = _make_decode_body(
        model, sampler, eos_token=eos_token, max_len=max_len
    )

    def run(params, kv, state, *extra):
        toks, positions, temps, seeds, steps, budgets, active = (
            _unpack_slot_state(state)
        )
        fin0 = (~active) | (steps >= budgets)
        if eos_token is not None:
            fin0 = fin0 | (toks == eos_token)
        ring0 = jnp.zeros((ring_capacity, toks.shape[0]), toks.dtype)
        valid0 = jnp.zeros((ring_capacity, toks.shape[0]), bool)

        def cond(carry):
            # carry[0][4] is the finish mask, carry[3] the cursor — the
            # same positions with or without the trailing digest dict
            return jnp.logical_and(
                ~jnp.all(carry[0][4]), carry[3] < ring_capacity
            )

        def body(carry):
            inner, ring, valid, it = carry[:4]
            live = ~inner[4]  # sampled-this-iteration rows
            if numerics:
                with numerics_tape(sites=_NUMERICS_SITES) as tape:
                    inner = step(params, temps, seeds, budgets, extra, inner)
                digs = merge_digest_trees(carry[4], tape.digests())
            else:
                inner = step(params, temps, seeds, budgets, extra, inner)
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, inner[1], it, 0
            )
            valid = jax.lax.dynamic_update_index_in_dim(valid, live, it, 0)
            if stream_cb is not None:
                stream_cb(inner[1], live, it)
            out = (inner, ring, valid, it + 1)
            return out + ((digs,) if numerics else ())

        init = ((kv, toks, positions, steps, fin0), ring0, valid0,
                jnp.int32(0))
        if numerics:
            init = init + (_zero_site_digests(),)
        res = jax.lax.while_loop(cond, body, init)
        (kv, _, _, _, _), ring, valid, it = res[:4]
        if numerics:
            return kv, ring, valid, it, res[4]
        return kv, ring, valid, it

    return run


def _make_spec_decode_body(
    model: Any,
    sampler,
    *,
    eos_token: Optional[int],
    max_len: int,
    speculate: int,
    ngram: int = 2,
):
    """Self-speculative draft/verify/accept decode body: the variable-
    advance sibling of ``_make_decode_body``, shared by the fused scan
    and the persistent while-loop exactly like the one-token body — so
    fused-vs-persistent identity again holds by construction.

    One iteration, entirely on device (no host sync is ever introduced —
    the PyGraph whole-capture rule the persistent loop is built on):

    1. DRAFT ``speculate`` candidate tokens per slot by prompt-lookup:
       find the most recent earlier occurrence of the slot's trailing
       ``ngram`` tokens in its own token history ``hist`` (the prompt +
       everything generated; no second model, no new weights) and
       propose the tokens that followed it.  A slot with no match
       proposes garbage — harmless, it just verifies to an accept
       length of 0.
    2. VERIFY all ``speculate + 1`` positions in ONE batched
       ``forward_decode`` call: the pending token plus the drafts ride
       as a (B, K+1) query block through the same
       ``slot_cached_attention`` path, each query row masked to its own
       depth ``pos + i``.  Row 0's logits are bit-identical to the
       one-token call's (every op on the path is query-row-independent),
       which is what makes greedy spec-vs-nonspec streams bit-identical
       rather than approximately equal.
    3. ACCEPT the longest draft prefix whose tokens equal the greedy
       targets of the previous row (``a`` matches ⇒ ``e = a + 1`` tokens
       emitted: the accepted drafts plus the one "free" token the
       verify computed after them).  Sampled rows (``temps > 0``) force
       ``a = 0`` so they advance exactly one token per iteration and
       the ``fold_in(seed, step)`` key schedule is untouched.  ``e`` is
       then truncated on device by the SAME finish rules the host walk
       applies — first EOS inside the block, remaining budget, cache
       end — so a slot can only finish at the LAST token of an
       iteration and the host re-derives identical finish reasons.

    KV safety under variable advance (the PR 3/6 frozen-write argument
    extended): the verify writes rows ``pos .. pos + K`` for every slot.
    Rows ``pos .. pos + e - 1`` hold K/V of exactly the accepted stream
    (the acceptance test guarantees the written candidates equal the
    true greedy continuation); rows ``pos + e .. pos + K`` hold
    rejected-lane K/V, but ``pos`` advances only by ``e``, so they sit
    beyond the slot's live depth and the next iteration's verify
    rewrites them before the visibility mask can ever reach them
    (overwrite-before-visible).  Rows past ``max_len`` are DROPPED by
    the multi-token scatter (``serve/kv_cache.py``) rather than clamped
    — a clamp would corrupt the last row, a flat unclamped scatter
    would collide into the next slot.

    ``step(params, temps, seeds, budgets, extra, carry)`` takes carry
    ``(kv, tok, pos, stp, fin, hist)`` — the one-token carry plus the
    (B, max_len) int32 token history — and returns ``(carry, y_block,
    cnt)``: the (B, K+1) verified token block and the per-slot emitted
    count (0 for frozen slots, else ``e``).  At ``e == 1`` every carry
    update reduces exactly to ``_make_decode_body``'s.
    """

    if speculate < 1:
        raise ValueError(f"speculate must be >= 1, got {speculate}")
    if ngram < 1:
        raise ValueError(f"ngram must be >= 1, got {ngram}")

    def step(params, temps, seeds, budgets, extra, carry):
        kv, tok, pos, stp, fin, hist = carry
        b = tok.shape[0]
        rows = jnp.arange(b)
        # the pending token enters the history at its own stream index.
        # Idempotent for host-known tokens; load-bearing for persistent
        # mode's deferred first tokens, which the host never saw.
        hist = hist.at[rows, jnp.clip(pos, 0, max_len - 1)].set(tok)

        # -- draft: most recent earlier occurrence of the trailing n-gram
        idx = jnp.arange(max_len)[None, :]
        match = (idx >= ngram - 1) & (idx < pos[:, None])
        for d in range(ngram):
            shifted = (
                hist
                if d == 0
                else jnp.pad(hist, ((0, 0), (d, 0)))[:, :max_len]
            )
            tgt = jnp.take_along_axis(
                hist, jnp.clip(pos - d, 0, max_len - 1)[:, None], axis=1
            )
            match = match & (shifted == tgt)
        j_best = jnp.max(jnp.where(match, idx, -1), axis=1)
        draft = jnp.take_along_axis(
            hist,
            jnp.clip(
                j_best[:, None] + 1 + jnp.arange(speculate)[None, :],
                0,
                max_len - 1,
            ),
            axis=1,
        ).astype(tok.dtype)

        # -- verify: one (B, K+1) forward through slot_cached_attention
        qtok = jnp.concatenate([tok[:, None], draft], axis=1)
        logits, kv = functional_call(
            model, params, (qtok, kv, pos) + extra, method="forward_decode"
        )
        logits = tap("logits", logits)  # the whole (B, K+1) verify block
        y1 = sampler(logits[:, 0, :], temps, seeds, stp)
        gre = jnp.argmax(logits, axis=-1).astype(tok.dtype)
        y_block = jnp.concatenate([y1[:, None], gre[:, 1:]], axis=1)

        # -- accept: longest draft prefix matching the greedy targets;
        # sampled rows pin the accept length to 0 (key schedule intact)
        m = (qtok[:, 1:] == y_block[:, :speculate]) & (temps <= 0.0)[:, None]
        acc = jnp.sum(jnp.cumprod(m.astype(jnp.int32), axis=1), axis=1)
        e = acc + 1
        jj = jnp.arange(1, speculate + 2)[None, :]
        if eos_token is not None:
            first_eos = jnp.min(
                jnp.where(y_block == eos_token, jj, speculate + 2), axis=1
            )
            e = jnp.minimum(e, first_eos)
        e = jnp.minimum(e, budgets - stp)
        e = jnp.minimum(e, max_len - pos)
        e = jnp.maximum(e, 1)
        last = jnp.take_along_axis(y_block, (e - 1)[:, None], axis=1)[:, 0]

        # emitted tokens extend the history; rejected lanes and frozen
        # slots are dropped, rows past max_len are dropped
        tgt_idx = pos[:, None] + jj
        writable = (
            (jj <= e[:, None]) & (~fin)[:, None] & (tgt_idx < max_len)
        )
        hist = hist.at[
            rows[:, None], jnp.where(writable, tgt_idx, max_len)
        ].set(y_block.astype(hist.dtype), mode="drop")

        new_tok = jnp.where(fin, tok, last)
        new_stp = jnp.where(fin, stp, stp + e)
        hit_eos = (
            (last == eos_token)
            if eos_token is not None
            else jnp.zeros_like(fin)
        )
        hit_len = new_stp >= budgets
        hit_full = pos + e >= max_len
        new_fin = fin | hit_eos | hit_len | hit_full
        new_pos = jnp.where(fin, pos, jnp.clip(pos + e, 0, max_len - 1))
        cnt = jnp.where(fin, 0, e).astype(jnp.int32)
        return (kv, new_tok, new_pos, new_stp, new_fin, hist), y_block, cnt

    return step


def _make_fused_spec_decode(
    model: Any,
    sampler,
    *,
    eos_token: Optional[int],
    max_len: int,
    decode_chunk: int,
    speculate: int,
    ngram: int = 2,
    numerics: bool = False,
):
    """Fused K-iteration speculative decode: ``_make_spec_decode_body``
    under a ``decode_chunk``-length ``lax.scan``.  Each scan step emits
    the full (B, K+1) verified block plus the per-slot emitted count, so
    the host walk can consume a VARIABLE number of tokens per iteration
    per slot while the device shapes stay static.

    Returns ``run(params, kv, state, hist, *extra) -> (kv, (chunk, B,
    K+1) token blocks, (chunk, B) counts)`` (``state``:
    ``pack_slot_state``, as for ``_make_fused_decode``).
    """

    step = _make_spec_decode_body(
        model,
        sampler,
        eos_token=eos_token,
        max_len=max_len,
        speculate=speculate,
        ngram=ngram,
    )

    def run(params, kv, state, hist, *extra):
        toks, positions, temps, seeds, steps, budgets, finished = (
            _unpack_slot_state(state)
        )
        init = (kv, toks, positions, steps, finished, hist)
        if not numerics:
            def body(carry, _):
                carry, y_block, cnt = step(
                    params, temps, seeds, budgets, extra, carry
                )
                return carry, (y_block, cnt)

            (kv, _, _, _, _, _), (ys, cs) = jax.lax.scan(
                body, init, None, length=decode_chunk
            )
            return kv, ys, cs

        def body(carry, _):
            inner, digs = carry
            with numerics_tape(sites=_NUMERICS_SITES) as tape:
                inner, y_block, cnt = step(
                    params, temps, seeds, budgets, extra, inner
                )
            digs = merge_digest_trees(digs, tape.digests())
            return (inner, digs), (y_block, cnt)

        (inner, digs), (ys, cs) = jax.lax.scan(
            body, (init, _zero_site_digests()), None, length=decode_chunk
        )
        return inner[0], ys, cs, digs

    return run


def _make_persistent_spec_decode(
    model: Any,
    sampler,
    *,
    eos_token: Optional[int],
    max_len: int,
    ring_capacity: int,
    speculate: int,
    ngram: int = 2,
    numerics: bool = False,
):
    """Persistent speculative decode: the SAME ``_make_spec_decode_body``
    under the ``lax.while_loop`` fixpoint drive of
    ``_make_persistent_decode``.  The output ring widens to one (B, K+1)
    verified block per iteration plus a (ring_capacity, B) count ring —
    ``cnts[it, b] > 0`` is the old valid mask, and its value is how many
    of the block's tokens slot ``b`` actually emitted.  One ring row per
    ITERATION (not per token): ring capacity still bounds iterations,
    each worth up to K+1 tokens, and ``host_syncs == ring_drains``
    exactly as before — speculation multiplies tokens per sync, it never
    adds a sync.

    Returns ``run(params, kv, state, hist, *extra) -> (kv, ring, cnts,
    iterations)`` (``state``: as for ``_make_persistent_decode``).
    """

    step = _make_spec_decode_body(
        model,
        sampler,
        eos_token=eos_token,
        max_len=max_len,
        speculate=speculate,
        ngram=ngram,
    )

    def run(params, kv, state, hist, *extra):
        toks, positions, temps, seeds, steps, budgets, active = (
            _unpack_slot_state(state)
        )
        fin0 = (~active) | (steps >= budgets)
        if eos_token is not None:
            fin0 = fin0 | (toks == eos_token)
        b = toks.shape[0]
        ring0 = jnp.zeros((ring_capacity, b, speculate + 1), toks.dtype)
        cnt0 = jnp.zeros((ring_capacity, b), jnp.int32)

        def cond(carry):
            # carry[0][4] is the finish mask, carry[3] the cursor — the
            # same positions with or without the trailing digest dict
            return jnp.logical_and(
                ~jnp.all(carry[0][4]), carry[3] < ring_capacity
            )

        def body(carry):
            inner, ring, cnts, it = carry[:4]
            if numerics:
                with numerics_tape(sites=_NUMERICS_SITES) as tape:
                    inner, y_block, cnt = step(
                        params, temps, seeds, budgets, extra, inner
                    )
                digs = merge_digest_trees(carry[4], tape.digests())
            else:
                inner, y_block, cnt = step(
                    params, temps, seeds, budgets, extra, inner
                )
            ring = jax.lax.dynamic_update_index_in_dim(ring, y_block, it, 0)
            cnts = jax.lax.dynamic_update_index_in_dim(cnts, cnt, it, 0)
            out = (inner, ring, cnts, it + 1)
            return out + ((digs,) if numerics else ())

        init = ((kv, toks, positions, steps, fin0, hist), ring0, cnt0,
                jnp.int32(0))
        if numerics:
            init = init + (_zero_site_digests(),)
        res = jax.lax.while_loop(cond, body, init)
        (kv, _, _, _, _, _), ring, cnts, it = res[:4]
        if numerics:
            return kv, ring, cnts, it, res[4]
        return kv, ring, cnts, it

    return run


def _decode_tokens(
    apply_step: Callable[[jax.Array, Any, Any], tuple],
    sample,
    cache,
    last_logits: jax.Array,
    key: jax.Array,
    n_new: int,
    pos0,
) -> jax.Array:
    """Sample ``n_new`` tokens with a scan.  ``apply_step(tok_col, cache,
    pos)`` runs one cached decode step at position ``pos = pos0 + i``;
    ``last_logits`` is (B, V) for the first token.  Returns (B, n_new)."""

    def step(carry, i):
        cache, last, k = carry
        k, sub = jax.random.split(k)
        tok = sample(last, sub)
        logits, cache = apply_step(tok[:, None], cache, pos0 + i)
        return (cache, logits[:, -1], k), tok

    (_, last, key2), toks = jax.lax.scan(
        step, (cache, last_logits, key), jnp.arange(n_new - 1)
    )
    _, sub = jax.random.split(key2)
    final_tok = sample(last, sub)
    return jnp.concatenate(
        [jnp.moveaxis(toks, 0, 1), final_tok[:, None]], axis=1
    )


def _cached_jit(
    model, store: str, cache_key, build, donate_argnums=(), out_shardings=None
):
    # jit cache lives ON the model so executables (which close over the
    # model) are collected with it rather than pinned by a module global.
    # out_shardings (a pytree prefix) must be passed explicitly for any
    # output NOT derived from a same-sharded input — jit does not
    # propagate input shardings into fresh outputs (the mesh serve
    # programs' sampled tokens/rings; same rule as optimizer state in
    # parallel/fsdp.optimizer_state_shardings).  Callers relying on it
    # must bake a mesh identity into cache_key: out_shardings is only
    # applied at the miss, so two engines sharing a key would silently
    # share the first engine's shardings.
    builders = model.__dict__.setdefault(store, {})
    if cache_key not in builders:
        kwargs = {}
        if out_shardings is not None:
            kwargs["out_shardings"] = out_shardings
        builders[cache_key] = jax.jit(
            build, donate_argnums=donate_argnums, **kwargs
        )
    return builders[cache_key]


def generate(
    model: Any,
    prompt: jax.Array,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    key: Optional[jax.Array] = None,
    params: Optional[dict] = None,
) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``prompt`` (B, S).

    ``temperature == 0`` is greedy; otherwise samples with the given
    temperature (``key`` required), optionally filtered to the ``top_k``
    highest-probability tokens and/or the ``top_p`` nucleus.  Returns
    (B, S + max_new_tokens).
    """
    if temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) requires a PRNG key")
    _check_sampling_args(top_k, top_p)
    params = params if params is not None else dict(model.named_parameters())
    if key is None:
        # deterministic default sampling key for greedy-path callers
        key = jax.random.PRNGKey(0)  # tdx-lint: disable=TDX102 -- default key, not param init
    b, s = prompt.shape
    if max_new_tokens <= 0:
        return prompt
    max_new = int(max_new_tokens)
    cfg = getattr(model, "cfg", None)
    limit = getattr(cfg, "max_seq_len", None) or getattr(
        cfg, "n_positions", None
    )
    if limit is not None and s + max_new > limit:
        # RoPE/positional tables clamp silently past the end; fail loudly
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new}) exceeds the "
            f"model's maximum sequence length {limit}"
        )

    def run(params, prompt, key):
        def apply_step(tokens, cache, pos):
            return functional_call(
                model, params, (tokens, cache, pos), method="forward_cached"
            )

        cache = model.init_cache(b, s + max_new)
        logits, cache = apply_step(prompt, cache, 0)
        toks = _decode_tokens(
            apply_step,
            _make_sampler(temperature, prompt.dtype, top_k, top_p),
            cache,
            logits[:, -1],
            key,
            max_new,
            s,
        )
        return jnp.concatenate([prompt, toks], axis=1)

    jitted = _cached_jit(
        model,
        "_generate_cache",
        (b, s, max_new, float(temperature), top_k, top_p),
        run,
    )
    return jitted(params, prompt, key)


def generate_encdec(
    model: Any,
    enc_tokens: jax.Array,
    max_new_tokens: int,
    *,
    start_token: int = 0,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    key: Optional[jax.Array] = None,
    params: Optional[dict] = None,
) -> jax.Array:
    """Encoder-decoder generation (T5-style).

    The encoder runs once; every decode step reuses the cached encoder K/V
    and the causal self-attention cache.  Decoding starts from
    ``start_token`` (T5's convention: the pad token, id 0) and returns the
    (B, max_new_tokens) generated ids (start token excluded).
    """
    if max_new_tokens <= 0:
        raise ValueError("max_new_tokens must be positive")
    if temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) requires a PRNG key")
    _check_sampling_args(top_k, top_p)
    params = params if params is not None else dict(model.named_parameters())
    if key is None:
        # deterministic default sampling key for greedy-path callers
        key = jax.random.PRNGKey(0)  # tdx-lint: disable=TDX102 -- default key, not param init
    b = enc_tokens.shape[0]
    max_new = int(max_new_tokens)

    def run(params, enc_tokens, key):
        def call(method, *args):
            return functional_call(model, params, args, method=method)

        def apply_step(tokens, cache, pos):
            return call("decode_step", tokens, cache, pos)

        enc = call("encode", enc_tokens)
        # the cache carries weight-derived parts (encoder K/V), so it must
        # be built under the functional params too
        cache = call("init_decoder_cache", enc, max_new)
        tok0 = jnp.full((b, 1), start_token, jnp.int32)
        logits, cache = apply_step(tok0, cache, 0)
        return _decode_tokens(
            apply_step,
            _make_sampler(temperature, jnp.int32, top_k, top_p),
            cache,
            logits[:, -1],
            key,
            max_new,
            1,
        )

    jitted = _cached_jit(
        model,
        "_generate_encdec_cache",
        (
            b,
            enc_tokens.shape[1],
            max_new,
            float(temperature),
            top_k,
            top_p,
            start_token,
        ),
        run,
    )
    return jitted(params, enc_tokens, key)
