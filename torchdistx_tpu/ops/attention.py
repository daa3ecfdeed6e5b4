"""Attention ops.

``multihead_attention`` is the single-device path: plain einsum + softmax,
which XLA fuses onto the MXU.  ``ring_attention`` is the sequence-parallel
path: Q stays put while K/V blocks rotate around the ``sp`` mesh axis via
``lax.ppermute`` (ICI neighbor exchanges), combined with an online-softmax
accumulator — blockwise/ring attention a la Liu et al., the capability the
reference lacks entirely (SURVEY §5.7 calls it green-field).

Shapes follow (batch, seq, heads, head_dim) throughout.  GQA is supported
by passing fewer KV heads; they are broadcast over query-head groups.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs.comm import record_collective as _record_comm

__all__ = [
    "multihead_attention",
    "sp_attention",
    "ring_attention",
    "ring_flash_attention",
    "ulysses_attention",
    "cached_attention",
    "slot_cached_attention",
    "latent_slot_cached_attention",
]


def _record_ring_pass(axis: str, n: int, blocks: tuple) -> None:
    """Book one ring pass's ``lax.ppermute`` traffic into the comm audit.

    The ``lax.scan`` body traces ONCE but executes ``n`` times (length=n,
    including the final home-coming hop that returns each block to its
    owner), so each rotating tensor contributes ``n`` ppermute ops of its
    per-device block bytes — the explicit static-trip-count accounting the
    ``obs.comm`` module docstring requires of loop-executed collectives.
    The textbook ring needs only ``n-1`` hops; this implementation pays
    the extra home-coming rotation to keep the carry structure static,
    and the audit books what actually executes.
    """
    for blk in blocks:
        _record_comm("ppermute", axis, blk, count=n, axis_size=n)


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.repeat(k, n_rep, axis=2)


def cached_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    cache: tuple,
    cache_pos,
    *,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
    use_flash: Optional[bool] = None,
    window: Optional[int] = None,
):
    """Incremental attention against a static-shape KV cache — the shared
    decode primitive behind every model's ``forward_cached``.

    ``q``/``k_new``/``v_new``: (B, S, H, D) projections of the new tokens
    (any positional encoding already applied).  ``cache`` is ``(k, v)`` of
    shape (B, max_seq, Hkv, D); the new keys/values are written at
    ``cache_pos`` (traced) and slot ``j`` is visible to query ``i`` iff
    ``j <= cache_pos + i``.  GQA-aware (Hq a multiple of Hkv).  ``scale``
    defaults to 1/sqrt(D) (pass 1.0 for T5's unscaled dot products);
    ``bias`` is an optional (H, S, max_seq) additive logit bias (T5's
    relative-position bias).  f32 softmax.  Returns (out, (ck, cv)).

    **Flash prefill**: the from-empty prefill (``cache_pos == 0`` as a
    STATIC int, S > 1, no bias) is mathematically ordinary causal
    attention over the new keys alone — no written-before-this-call cache
    slot is visible — so it routes through the pallas flash kernel when
    ``use_flash`` resolves on (``resolve_use_flash``: auto = TPU).  That
    is the path ``generate()`` takes for every prompt, so long-context
    prefill stops materializing the (S, max_seq) logits matrix that OOMs
    at 8k+.  Mid-cache chunked prefill (``cache_pos`` traced or > 0)
    stays on the jnp path.
    """
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    b, s, hq, d = q.shape
    ck, cv = cache
    ck = lax.dynamic_update_slice(
        ck, k_new.astype(ck.dtype), (0, cache_pos, 0, 0)
    )
    cv = lax.dynamic_update_slice(
        cv, v_new.astype(cv.dtype), (0, cache_pos, 0, 0)
    )
    from .flash_attention import flash_attention, resolve_use_flash

    if (
        bias is None
        and s > 1
        and isinstance(cache_pos, (int, np.integer))
        and int(cache_pos) == 0
        and resolve_use_flash(use_flash)
    ):
        # pad the sequence to a lane multiple so arbitrary (odd/prime)
        # prompt lengths keep MXU-shaped blocks instead of shrinking the
        # kernel's block size toward 1.  Equal q/k padding preserves the
        # end-aligned causal mask for every real query (row i still sees
        # exactly keys 0..i); padded rows are sliced off.
        pad = (-s) % 128
        if pad:
            widen = lambda a: jnp.pad(  # noqa: E731
                a, ((0, 0), (0, pad), (0, 0), (0, 0))
            )
            out = flash_attention(
                widen(q), widen(k_new), widen(v_new),
                causal=True, scale=scale, window=window,
            )[:, :s]
        else:
            out = flash_attention(
                q, k_new, v_new, causal=True, scale=scale,
                window=window,
            )
        return out, (ck, cv)
    max_seq, hkv = ck.shape[1], ck.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if (
        window is not None
        and bias is None
        and s == 1
        and window < max_seq
    ):
        # Windowed single-token decode: attend a W-slice of the cache
        # instead of the full max_seq band — O(window) per generated
        # token.  The slice ends at the newest token; when fewer than
        # ``window`` tokens exist yet the leading slots are masked.
        start = jnp.clip(cache_pos + s - window, 0, max_seq - window)
        kw = lax.dynamic_slice_in_dim(ck, start, window, axis=1)
        vw = lax.dynamic_slice_in_dim(cv, start, window, axis=1)
        kw = _repeat_kv(kw, hq // hkv)
        vw = _repeat_kv(vw, hq // hkv)
        logits = (
            jnp.einsum("bqhd,bkhd->bhqk", q, kw).astype(jnp.float32) * scale
        )
        pos = start + jnp.arange(window)  # global cache slots in the slice
        # the band's lower edge is enforced by the slice start itself
        # (start >= cache_pos + 1 - window by construction); only the
        # not-yet-written upper slots need masking
        visible = pos[None, :] <= cache_pos
        logits = jnp.where(visible[None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vw)
        return out, (ck, cv)
    kk = _repeat_kv(ck, hq // hkv)
    vv = _repeat_kv(cv, hq // hkv)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias[None].astype(jnp.float32)
    visible = (
        jnp.arange(max_seq)[None, :] <= cache_pos + jnp.arange(s)[:, None]
    )
    if window is not None:
        visible = visible & (
            jnp.arange(max_seq)[None, :]
            > cache_pos + jnp.arange(s)[:, None] - window
        )
    logits = jnp.where(visible[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
    return out, (ck, cv)


def _slot_attend(
    q: jax.Array,
    ck: jax.Array,
    cv: jax.Array,
    positions: jax.Array,
    scale: Optional[float],
    window: Optional[int],
) -> jax.Array:
    """The jnp per-slot attend shared by the contiguous and paged decode
    paths: ``ck``/``cv`` are (B, max_seq, Hkv, D) — a 4-D view of the
    stored slab or of a page-table gather of the pools — and row ``b``
    attends rows
    ``j <= positions[b]`` (within the trailing ``window`` when set).  One
    definition so the two layouts can never diverge bitwise: a gathered
    view holds the same visible values as the slab, and the masked tail
    (bucket padding, stale pages) contributes exactly-zero probability
    either way."""
    b, s, hq, d = q.shape
    max_seq, hkv = ck.shape[1], ck.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # GQA broadcast mirrors the scalar path's _repeat_kv + einsum exactly.
    # A grouped einsum (query heads folded onto their kv head) would skip
    # materializing the repeated cache — measured here, it changes the
    # contraction's bitwise result, and bit-identity with single-request
    # decode is this primitive's contract (tests/test_serve.py); revisit
    # together with the scalar path if that trade is renegotiated.
    kk = _repeat_kv(ck, hq // hkv)
    vv = _repeat_kv(cv, hq // hkv)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
    slots = jnp.arange(max_seq)[None, :]
    visible = slots <= positions[:, None]  # (B, max_seq)
    if window is not None:
        visible = visible & (slots > positions[:, None] - window)
    logits = jnp.where(visible[:, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vv)


def _slot_attend_block(
    q: jax.Array,
    ck: jax.Array,
    cv: jax.Array,
    positions: jax.Array,
    scale: Optional[float],
) -> jax.Array:
    """Multi-token sibling of :func:`_slot_attend` for the speculative
    verify block: ``q`` is (B, S, Hq, D) and query row ``i`` of slot
    ``b`` attends cache rows ``j <= positions[b] + i`` — the per-slot
    shift of :func:`cached_attention`'s S-token visibility template.
    Same ``_repeat_kv`` + einsum + f32-softmax op chain as
    ``_slot_attend``; every op is row-independent, so row 0 is bitwise
    the S == 1 result (the spec bit-identity contract)."""
    b, s, hq, d = q.shape
    max_seq, hkv = ck.shape[1], ck.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kk = _repeat_kv(ck, hq // hkv)
    vv = _repeat_kv(cv, hq // hkv)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
    slots = jnp.arange(max_seq)[None, None, :]
    depths = positions[:, None] + jnp.arange(s)[None, :]  # (B, S)
    visible = slots <= depths[:, :, None]  # (B, S, max_seq)
    logits = jnp.where(visible[:, None, :, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vv)


def slot_cached_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    cache: tuple,
    positions: jax.Array,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    use_flash: Optional[bool] = None,
    page_tables: Optional[jax.Array] = None,
):
    """Single-token batched decode where each batch row sits at its OWN
    cache depth — the continuous-batching sibling of
    :func:`cached_attention` (whose ``cache_pos`` is one scalar for the
    whole batch).  Rows are independent serving *slots*: row ``b``'s new
    K/V are written at ``positions[b]`` and its query attends cache
    slots ``j <= positions[b]``.

    ``q``/``k_new``/``v_new``: (B, S, H, D) projections of each slot's
    next token(s), positional encoding already applied at that slot's
    own position(s).  ``S == 1`` is the plain decode step; ``S > 1`` is
    the speculative verify block (``ServeEngine(speculate=K)`` passes
    ``S = K + 1`` candidates), where row ``i`` writes at
    ``positions[b] + i`` and attends ``j <= positions[b] + i``.
    ``cache`` is ``(k, v)`` in the serve engine's STORED layout
    (``serve/kv_cache.py``): shape (B, max_seq, Hkv * D), the head tail
    merged — the decode kernel's operand as it is, so the compiled
    program never relayouts the cache.  The new rows are flattened
    ``(B, S, Hkv, D) -> (B, S, Hkv * D)`` before the write; the jnp
    attends take a 4-D view of what they read (free on CPU, a copy on
    the chip, where those paths already materialize ``_repeat_kv``
    copies of the whole cache).  ``positions`` is (B,) int32.
    Row-for-row this is exactly the
    ``s == 1`` path of :func:`cached_attention` (the same row written,
    same visibility rule, f32 softmax), so a slot's decode stream is
    bit-identical to single-request decode at the same position.
    GQA-aware; ``window`` applies the same end-aligned sliding band as
    the scalar path.  Returns (out, (ck, cv)).

    **Flash decode**: when ``use_flash`` resolves on
    (``resolve_use_flash``: auto = TPU) and no ``window`` is set, the
    post-write attend routes through the pallas slot-paged kernel
    (``ops.decode_attention``): per-slot length-masked blocks streamed
    off the slab, no ``_repeat_kv`` copy, no (B, H, max_seq) logits
    band — the hot op of the serve engine's fused decode loop.  The
    write itself (one scatter of the slots' rows per cache array,
    ``serve/kv_cache.scatter_slot_tokens``) is identical on both
    paths, and the kernel's single-K-block configuration is
    bit-identical to the jnp path in interpret mode
    (``ops/decode_attention.py`` docstring); windowed decode stays jnp.

    **Paged cache**: with ``page_tables`` (B, pages_per_slot) int32 set,
    ``cache`` is instead the per-layer page pools of shape
    ``(num_pages, page_size, Hkv * D)`` and row ``b``'s logical cache is
    the concatenation of the pages ``page_tables[b]`` names.  The new
    K/V are scattered to ``page_tables[b, positions[b] // page_size]``
    at offset ``positions[b] % page_size``; the attend either runs the
    paged pallas kernel (K/V gathered page-by-page through the
    scalar-prefetched table, block == page) or gathers the logical view
    and applies the IDENTICAL jnp math as the contiguous path
    (``_slot_attend``) — a gather reproduces the slab's visible values
    bitwise, so paged and contiguous greedy streams are bit-identical
    (the engine-level contract tests/test_serve.py pins).

    **Quantized cache** (``ServeEngine(kv_dtype="int8")``): ``cache`` is
    the 4-tuple ``(k, v, k_scale, v_scale)`` — int8 data plus f32
    per-row per-head scales of shape (B, max_seq, Hkv)
    (``serve/kv_cache.py``).  New K/V quantize
    on write (data and scale rows ride the same scatter indices), the
    pallas kernels dequantize blocks as they stream through VMEM
    (``k_scale=``/``v_scale=`` operands), and the jnp paths attend the
    dequantized view — kernel-vs-jnp parity therefore holds with the
    SAME bounds as the f32 cache, both paths reading identical
    dequantized values.  Returns the cache in the same 4-tuple form.
    """
    b, s, hq, d = q.shape
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if s != 1 and window is not None:
        raise ValueError(
            f"multi-token slot decode does not support window "
            f"(got S={s}, window={window})"
        )
    from ..serve.kv_cache import (
        heads_view,
        paged_scatter_tokens,
        paged_view,
        scatter_slot_tokens,
        stored_rows,
    )
    from .flash_attention import resolve_use_flash

    paged = page_tables is not None
    # the new rows in the cache's own form — one array per cache array
    # (K, V and, quantized, their scale rows), head tail merged, cache
    # dtype.  The CACHE is never reshaped on its way to the kernel.
    rows = stored_rows(cache, k_new, v_new)

    # -- the write: S rows per slot, each array one scatter of (slot |
    # page, row) indexed rows (serve/kv_cache.py).  S == 1 is the decode
    # step (paged: a slot's current tail page is exclusively owned —
    # sharing is full-prefix-pages only — so the scatter indices of
    # ACTIVE slots never collide; retired slots' tables all name the
    # scratch page, whose bits are never visible to any query); S > 1
    # the speculative verify block (ServeEngine(speculate=K)), S = K + 1
    # candidate rows per slot.  Rows past max_len are dropped, never
    # clamped or wrapped; the engine's positions are always in range.
    if paged:
        ps = cache[0].shape[1]
        cache = tuple(
            paged_scatter_tokens(c, x, page_tables, positions, ps)
            for c, x in zip(cache, rows)
        )
    else:
        cache = tuple(
            scatter_slot_tokens(c, x, positions) for c, x in zip(cache, rows)
        )

    # -- the attend.  Every op on either path is query-row independent,
    # so row i of an S > 1 block is bit-identical to the S == 1 call at
    # position positions[b] + i with the same cache prefix — the
    # property the engine's greedy spec-vs-nonspec bit-identity contract
    # rests on.
    if window is None and resolve_use_flash(use_flash):
        from . import decode_attention as da

        scales = dict(zip(("k_scale", "v_scale"), cache[2:]))
        if paged:
            out = da.paged_decode_attention_block(
                q, cache[0], cache[1], page_tables, positions,
                scale=scale, **scales,
            )
        else:
            out = da.decode_attention_block(
                q, cache[0], cache[1], positions, scale=scale, **scales
            )
        return out, cache
    # jnp: a 4-D view of what is read — the slab, or each slot's logical
    # row gathered through its page table
    hkv = k_new.shape[2]
    if paged:
        vk, vv = paged_view([cache], page_tables, hkv)[0]
    else:
        vk, vv = heads_view(cache, hkv)
    if s == 1:
        out = _slot_attend(q, vk, vv, positions, scale, window)
    else:
        out = _slot_attend_block(q, vk, vv, positions, scale)
    return out, cache


def latent_slot_cached_attention(
    q: jax.Array,
    row_new: jax.Array,
    cache: tuple,
    positions: jax.Array,
    *,
    value_width: int,
    scale: float,
    use_flash: Optional[bool] = None,
):
    """The latent-cache (MLA) sibling of :func:`slot_cached_attention`:
    one decode token per serving slot in the ABSORBED form.

    ``q``: (B, H, W) absorbed queries ``[q_nope W_uk ; q_rope]``, ``W =
    latent + rope width``.  ``row_new``: (B, 1, W), each slot's new cache
    row ``[c ; k_r]`` (norm and rope applied).  ``cache``: the engine's
    latent entry, a ``LatentEntry(latent)`` of shape (slots, max_len, W) —
    the kernel's operand as it is stored (``serve/kv_cache.py``).  The
    row is written at ``positions[b]`` by the slab's one scatter
    (``scatter_slot_tokens``), then slot ``b`` attends rows ``j <=
    positions[b]``: the Pallas kernel ``tdx_latent_decode_attention``
    when ``use_flash`` resolves on (auto = TPU), else the jnp path of
    the same math (``ops.latent_decode_attention.latent_attend``).  Every
    visible row is read once, for the score and for the value (its first
    ``value_width`` lanes).  Returns ``(o~ (B, H, value_width),
    LatentEntry(latent))``; ``o~`` still goes through ``W_uv``."""
    from ..serve.kv_cache import LatentEntry, scatter_slot_tokens
    from . import latent_decode_attention as lda
    from .flash_attention import resolve_use_flash

    (latent,) = cache
    latent = scatter_slot_tokens(latent, row_new, positions)
    attend = (
        lda.latent_decode_attention
        if resolve_use_flash(use_flash)
        else lda.latent_attend
    )
    out = attend(q, latent, positions, value_width=value_width, scale=scale)
    return out, LatentEntry(latent)


def multihead_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """(B, Sq, Hq, D) x (B, Skv, Hkv, D)^2 -> (B, Sq, Hq, D).

    ``window``: sliding-window attention (query ``i`` sees keys
    ``(i - window, i]`` end-aligned), the Mistral/Mixtral scheme;
    requires ``causal``."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if hq != hkv:
        k = _repeat_kv(k, hq // hkv)
        v = _repeat_kv(v, hq // hkv)
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # f32 softmax accumulation regardless of input dtype (TPU practice)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        if window is not None:
            mask = mask & jnp.triu(
                jnp.ones((sq, skv), bool), k=skv - sq - (window - 1)
            )
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)



def _ring_bias_block(bias, j, skv):
    """The held block's bias columns: shard j's keys occupy global columns
    [j * skv, (j + 1) * skv).  One definition for the jnp ring and both
    flash-ring passes, so the hop->column mapping can never desynchronize
    between the reference and kernel paths."""
    if bias is None:
        return None
    return lax.dynamic_slice_in_dim(bias, j * skv, skv, axis=2)


def _validate_ring_bias(name, bias, hq, sq, n, skv):
    if bias is not None and bias.shape != (hq, sq, n * skv):
        # dynamic_slice would CLAMP a too-short key dim (e.g. a bias
        # mistakenly sharded on its key axis) into silently wrong logits
        raise ValueError(
            f"{name} bias shape {bias.shape} != (H, sq_local, "
            f"S_global) = {(hq, sq, n * skv)} — keep the key dim of the "
            "bias UNsharded (in_specs P(None, axis, None))"
        )


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str,
    causal: bool = True,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Ring attention over sequence shards.  Must run inside ``shard_map``
    with the sequence dim sharded over ``axis``.

    Each of the N ring steps attends Q's local block against one K/V block,
    then rotates K/V to the next neighbor (``lax.ppermute`` — a pure ICI
    neighbor hop).  The online-softmax state (running max, running sum,
    weighted accumulator) makes the result exactly equal to full attention.

    Causality is handled blockwise: with Q-block index ``i`` and the K/V
    block currently held being ``j``, the block is fully visible when
    ``j < i``, diagonal (``j == i``) applies the local causal mask, and
    future blocks contribute nothing.

    ``bias``: optional additive logit bias of shape
    (H, sq_local, S_global) — this shard's global query rows against ALL
    key positions (T5's relative-position bias under sequence
    parallelism).  The rotating block index selects each hop's column
    slice, so only O(S) bias per device is needed.
    """
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    _validate_ring_bias("ring_attention", bias, hq, sq, n, skv)
    # GQA: keep K/V at hkv heads while they travel the ring (1/n_rep the
    # ppermute bytes — the whole point of GQA on the long-context path) and
    # broadcast over query-head groups only inside each local block step.
    n_rep = hq // hkv
    scale_ = scale if scale is not None else 1.0 / math.sqrt(d)

    perm = [(i, (i + 1) % n) for i in range(n)]
    neg_inf = jnp.float32(-1e30)
    local_mask = jnp.tril(jnp.ones((sq, skv), bool))

    def block(carry, _):
        acc, row_max, row_sum, kb, vb, j = carry
        kb_full = _repeat_kv(kb, n_rep)
        vb_full = _repeat_kv(vb, n_rep)
        logits = (
            jnp.einsum("bqhd,bkhd->bhqk", q, kb_full).astype(jnp.float32)
            * scale_
        )
        if bias is not None:
            bias_blk = _ring_bias_block(bias, j, skv)
            logits = logits + bias_blk[None].astype(jnp.float32)
        if causal:
            visible = jnp.where(
                j < idx,
                jnp.ones((sq, skv), bool),
                jnp.where(j == idx, local_mask, jnp.zeros((sq, skv), bool)),
            )
            logits = jnp.where(visible, logits, neg_inf)
        blk_max = jnp.max(logits, axis=-1)
        new_max = jnp.maximum(row_max, blk_max)
        correction = jnp.exp(row_max - new_max)
        probs = jnp.exp(logits - new_max[..., None])
        new_sum = row_sum * correction + probs.sum(axis=-1)
        acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", probs, vb_full.astype(jnp.float32)
        )
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        j = lax.ppermute(j, axis, perm)
        return (acc, new_max, new_sum, kb, vb, j), None

    acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    max0 = jnp.full((b, hq, sq), neg_inf)
    sum0 = jnp.zeros((b, hq, sq), jnp.float32)
    _record_ring_pass(axis, n, (k, v, idx))
    (acc, row_max, row_sum, _, _, _), _ = lax.scan(
        block, (acc0, max0, sum0, k, v, idx), None, length=n
    )
    out = acc / jnp.maximum(row_sum[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash-backed ring attention
# ---------------------------------------------------------------------------
#
# ``ring_attention`` above computes each ring step with a full
# (sq_local x skv_local) f32 logits matrix — fine for modest shards, but at
# pod-scale long context (e.g. 64k over 8 devices = 8k-per-shard blocks)
# that per-step matrix is exactly the memory wall the flash kernel exists
# to remove.  ``ring_flash_attention`` runs the SAME ring schedule with the
# pallas kernel per block: the kernel streams K/V through VMEM and exports
# its per-row online-softmax state (m, l), and the ring combines blocks
# with the standard two-level online-softmax merge.  Backward is a second
# ring pass with the saved global LSE: dK/dV accumulators rotate WITH
# their K/V blocks (each device adds its contribution to the block it
# currently holds; after n hops block and gradient land home together),
# and the per-block math runs through the pallas FlashAttention-2
# backward kernels seeded with the global LSE — VMEM-blocked like the
# forward, no per-hop logits matrix.
#
# GQA rides the kernel's native head-group mapping: K/V travel and are
# consumed at hkv heads (the jnp ring broadcasts to hq heads inside each
# step); gradient head-group reduction happens in the backward einsum.


def _ring_combine(acc, m, l, raw_j, m_j, l_j):
    """Two-level online-softmax merge: fold one block's RAW f32
    accumulator (sum of exp(logits - m_j) @ V, not normalized — see
    ``_flash_forward(return_residuals=True)``) and (m, l) state into the
    running accumulator.  Pure f32 throughout; normalization happens once
    after the last block."""
    new_m = jnp.maximum(m, m_j)
    alpha = jnp.exp(m - new_m)
    beta = jnp.exp(m_j - new_m)
    raw_j = jnp.transpose(raw_j, (0, 2, 1, 3))
    acc = acc * alpha[..., None] + raw_j * beta[..., None]
    return acc, new_m, l * alpha + l_j * beta


def _ring_bwd_block(
    prep, khb, vhb, bias_blk, *,
    b, hq, hkv, diag, scale, block_q, block_k, interpret,
):
    """Gradient contributions of one held K/V block, via the pallas
    FlashAttention-2 backward kernels seeded with the GLOBAL row LSE —
    each block's partial softmax ``p = exp(logits - lse)`` is then exact,
    so the kernel outputs are this block's exact gradient contributions
    (``_flash_backward`` docstring).  ``prep`` is the hoisted
    loop-invariant operand tuple (``_prepare_flash_bwd``); K/V arrive and
    gradients leave HEAD-MAJOR, matching the ring carry.  ``diag``
    applies the local causal mask (static per cond-branch); contributions
    accumulate across hops in f32.  With ``bias_blk`` (this block's
    column slice) the kernels stream the bias and a dbias slice is
    returned (each device owns its query rows' bias gradient — no
    cross-device reduction)."""
    from .flash_attention import _flash_backward_core, _flash_dbias

    qh, doh, oh, lse_b = prep
    dqh, dk_part, dv_part = _flash_backward_core(
        qh, doh, oh, lse_b, khb, vhb,
        b=b, hq=hq, hkv=hkv,
        causal=diag, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        dq_dtype=jnp.float32, part_dtype=jnp.float32,
        bias=bias_blk,
    )
    n_rep = hq // hkv
    if n_rep > 1:
        # fold per-query-head partials onto the kv heads (g-major groups)
        skv, d = dk_part.shape[1:]
        dk_part = (
            dk_part.reshape(b, hkv, n_rep, skv, d).sum(2).reshape(-1, skv, d)
        )
        dv_part = (
            dv_part.reshape(b, hkv, n_rep, skv, d).sum(2).reshape(-1, skv, d)
        )
    db_blk = None
    if bias_blk is not None:
        db_blk = _flash_dbias(
            qh, doh, oh, lse_b, khb, vhb, bias_blk,
            b=b, hq=hq, hkv=hkv,
            causal=diag, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
        ).astype(jnp.float32)
    return dqh, dk_part, dv_part, db_blk


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ring_flash_vjp(
    q, k, v, bias, axis, causal, scale, block_q, block_k, interpret
):
    out, _ = _ring_flash_fwd(
        q, k, v, bias, axis, causal, scale, block_q, block_k, interpret
    )
    return out


def _ring_flash_fwd(
    q, k, v, bias, axis, causal, scale, block_q, block_k, interpret
):
    from .flash_attention import _flash_forward

    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    scale_ = scale if scale is not None else 1.0 / math.sqrt(d)
    perm = [(i, (i + 1) % n) for i in range(n)]
    flash = functools.partial(
        _flash_forward,
        scale=scale_,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        return_residuals=True,
    )

    def step(carry, _):
        acc, m, l, kb, vb, j = carry

        def make_branch(diag_mask):
            def branch(ops):
                a, mm, ll = ops
                # O(S) bias per device total (_ring_bias_block)
                blk_bias = _ring_bias_block(bias, j, skv)
                return _ring_combine(
                    a, mm, ll,
                    *flash(q, kb, vb, causal=diag_mask, bias=blk_bias),
                )

            return branch

        full, diag = make_branch(False), make_branch(True)
        if causal:
            acc, m, l = lax.cond(
                j == idx,
                diag,
                lambda ops: lax.cond(j < idx, full, lambda o: o, ops),
                (acc, m, l),
            )
        else:
            acc, m, l = full((acc, m, l))
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        j = lax.ppermute(j, axis, perm)
        return (acc, m, l, kb, vb, j), None

    acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    m0 = jnp.full((b, hq, sq), jnp.float32(-1e30))
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    _record_ring_pass(axis, n, (k, v, idx))
    (acc, m, l, _, _, _), _ = lax.scan(
        step, (acc0, m0, l0, k, v, idx), None, length=n
    )
    safe_l = jnp.maximum(l, 1e-30)
    out = jnp.transpose(acc / safe_l[..., None], (0, 2, 1, 3)).astype(q.dtype)
    lse = m + jnp.log(safe_l)  # global per-row logsumexp, saved for bwd
    return out, lse


def _ring_flash_fwd_rule(
    q, k, v, bias, axis, causal, scale, block_q, block_k, interpret
):
    out, lse = _ring_flash_fwd(
        q, k, v, bias, axis, causal, scale, block_q, block_k, interpret
    )
    return out, (q, k, v, bias, out, lse)


def _ring_flash_bwd_rule(
    axis, causal, scale, block_q, block_k, interpret, res, g
):
    q, k, v, bias, out, lse = res
    from .flash_attention import _prepare_flash_bwd

    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    scale_ = scale if scale is not None else 1.0 / math.sqrt(d)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # loop-invariant operands hoisted out of the ring: transposes + the
    # lse lane-broadcast happen once, not per hop
    prep = _prepare_flash_bwd(q, g, out, lse)
    # K/V and their gradient accumulators travel the ring HEAD-MAJOR (the
    # kernels' layout) so hops carry no per-step transposes either
    kh = jnp.transpose(k, (0, 2, 1, 3)).reshape(b * hkv, skv, d)
    vh = jnp.transpose(v, (0, 2, 1, 3)).reshape(b * hkv, skv, d)

    def step(carry, _):
        dq, db, kb, vb, dkb, dvb, j = carry

        def make_branch(diag_mask):
            def branch(ops):
                dq_, db_, dkb_, dvb_, kb_, vb_ = ops
                bias_blk = _ring_bias_block(bias, j, skv)
                dq_c, dk_c, dv_c, db_c = _ring_bwd_block(
                    prep, kb_, vb_, bias_blk,
                    b=b, hq=hq, hkv=hkv,
                    diag=diag_mask, scale=scale_,
                    block_q=block_q, block_k=block_k, interpret=interpret,
                )
                if db_c is not None:
                    # each column block visits this device exactly once,
                    # so the slice write is the whole contribution
                    db_ = lax.dynamic_update_slice_in_dim(
                        db_, db_c, j * skv, axis=2
                    )
                return dq_ + dq_c, db_, dkb_ + dk_c, dvb_ + dv_c

            return branch

        full, diag = make_branch(False), make_branch(True)
        ops = (dq, db, dkb, dvb, kb, vb)
        if causal:
            dq, db, dkb, dvb = lax.cond(
                j == idx,
                diag,
                lambda o: lax.cond(
                    j < idx, full, lambda o_: (o_[0], o_[1], o_[2], o_[3]), o
                ),
                ops,
            )
        else:
            dq, db, dkb, dvb = full(ops)
        # gradient buffers travel WITH their K/V blocks: after n hops both
        # land back on the owning device with all contributions summed
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        dkb = lax.ppermute(dkb, axis, perm)
        dvb = lax.ppermute(dvb, axis, perm)
        j = lax.ppermute(j, axis, perm)
        return (dq, db, kb, vb, dkb, dvb, j), None

    dq0 = jnp.zeros((b * hq, sq, d), jnp.float32)
    # bias grad is per-device query rows x ALL key columns — O(S), the
    # same layout as the bias input; a scalar placeholder when bias-free
    db0 = (
        jnp.zeros((hq, sq, n * skv), jnp.float32)
        if bias is not None
        else jnp.zeros((), jnp.float32)
    )
    dk0 = jnp.zeros((b * hkv, skv, d), jnp.float32)
    dv0 = jnp.zeros((b * hkv, skv, d), jnp.float32)
    # five tensors rotate in the backward ring: the K/V blocks AND their
    # f32 gradient accumulators, plus the block index
    _record_ring_pass(axis, n, (kh, vh, dk0, dv0, idx))
    (dqh, dbh, _, _, dkh, dvh, _), _ = lax.scan(
        step, (dq0, db0, kh, vh, dk0, dv0, idx), None, length=n
    )
    dq = jnp.transpose(dqh.reshape(b, hq, sq, d), (0, 2, 1, 3))
    dk = jnp.transpose(dkh.reshape(b, hkv, skv, d), (0, 2, 1, 3))
    dv = jnp.transpose(dvh.reshape(b, hkv, skv, d), (0, 2, 1, 3))
    dbias = dbh.astype(bias.dtype) if bias is not None else None
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        dbias,
    )


_ring_flash_vjp.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str,
    causal: bool = True,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ring attention with the pallas flash kernel per block.

    Same schedule and exact-result guarantee as :func:`ring_attention`
    (must run inside ``shard_map`` with the sequence dim sharded over
    ``axis``), but each ring step streams the held K/V block through the
    flash kernel instead of materializing an (sq x skv) f32 logits
    matrix — per-device memory stays flat as shard sizes grow, which is
    what makes pod-scale long context (8k+ per shard) trainable.

    ``bias``: optional additive logit bias of shape
    (H, sq_local, S_global) — this shard's global query rows against ALL
    key positions (T5's relative-position bias under sequence
    parallelism, same layout as :func:`ring_attention`).  Each hop
    streams the held block's column slice into the kernels; the backward
    emits the dbias slice this device's query rows own (no cross-device
    reduction).

    Differentiable via a whole-ring custom VJP: backward is a second ring
    pass with the saved global LSE; dK/dV accumulators rotate with their
    blocks and each block's contributions come from the pallas
    FlashAttention-2 backward kernels (``_flash_backward``).
    """
    if causal and q.shape[1] != k.shape[1]:
        raise ValueError(
            "causal ring attention requires equal per-shard query and key "
            f"lengths, got {q.shape[1]} vs {k.shape[1]}"
        )
    if bias is not None:
        _validate_ring_bias(
            "ring_flash_attention", bias, q.shape[2], q.shape[1],
            lax.axis_size(axis), k.shape[1],
        )
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return _ring_flash_vjp(
        q, k, v, bias, axis, causal, scale, block_q, block_k, interpret
    )


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str,
    causal: bool = True,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism: the
    other standard long-context strategy next to :func:`ring_attention`.

    Inside ``shard_map`` with the sequence dim sharded over ``axis``:
    one all-to-all reshards (seq-sharded, all heads) -> (full seq,
    heads/n), attention runs LOCALLY over the full sequence with the
    head slice (the flash kernel when available — composes for free,
    since post-reshard attention is ordinary single-device attention),
    and a second all-to-all reshards back.  Communication is 2
    all-to-alls of O(S*D/n) per device versus the ring's n ppermute
    hops; attention math is bit-identical to the unsharded computation
    (no online-softmax recombination at all).

    Requires query AND kv head counts divisible by the axis size (GQA
    works when ``hkv % n == 0``); prefer the ring for very wide-group
    GQA or head counts that don't divide.
    """
    n = lax.axis_size(axis)
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    if hq % n != 0 or hkv % n != 0:
        raise ValueError(
            f"ulysses_attention needs head counts divisible by the axis "
            f"size: hq={hq}, hkv={hkv}, |{axis}|={n} — use ring attention "
            "for non-dividing head counts"
        )
    # (b, s/n, h, d) -> (b, s, h/n, d): split heads, concat sequence
    for t in (q, k, v):
        _record_comm("all_to_all", axis, t, axis_size=n)
    qg = lax.all_to_all(q, axis, split_axis=2, concat_axis=1, tiled=True)
    kg = lax.all_to_all(k, axis, split_axis=2, concat_axis=1, tiled=True)
    vg = lax.all_to_all(v, axis, split_axis=2, concat_axis=1, tiled=True)
    from .flash_attention import resolve_use_flash

    if resolve_use_flash(use_flash):
        from .flash_attention import flash_attention

        out = flash_attention(qg, kg, vg, causal=causal, scale=scale)
    else:
        out = multihead_attention(qg, kg, vg, causal=causal, scale=scale)
    # inverse reshard: (b, s, h/n, d) -> (b, s/n, h, d)
    _record_comm("all_to_all", axis, out, axis_size=n)
    return lax.all_to_all(out, axis, split_axis=1, concat_axis=2, tiled=True)


def sp_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str,
    mode: str = "ring",
    causal: bool = True,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """The one sequence-parallel dispatch shared by the model families
    (Llama/GPT-2/Mixtral/T5): "ring" routes to the flash-backed ring when
    ``use_flash`` resolves on and the jnp ring otherwise; "ulysses" runs
    the all-to-all strategy (no bias support — T5 must use the ring).
    One definition so mode selection, validation, and future parameters
    can never diverge between models."""
    from .flash_attention import resolve_use_flash

    if mode == "ulysses":
        if bias is not None:
            raise ValueError(
                "ulysses sequence parallelism does not support an additive "
                "bias; use mode='ring'"
            )
        return ulysses_attention(
            q, k, v, axis=axis, causal=causal, scale=scale,
            use_flash=use_flash,
        )
    if mode != "ring":
        raise ValueError(f"sp mode must be 'ring' or 'ulysses', got {mode!r}")
    if resolve_use_flash(use_flash):
        return ring_flash_attention(
            q, k, v, axis=axis, causal=causal, scale=scale, bias=bias
        )
    return ring_attention(
        q, k, v, axis=axis, causal=causal, scale=scale, bias=bias
    )
