"""Pallas slot-paged decode attention for the serving engine.

One generated token per serving *slot*, each slot at its own cache depth:
the hot inner op of ``ServeEngine``'s fused decode loop.  The jnp
reference (``ops.attention.slot_cached_attention``) materializes the full
``(B, H, 1, max_len)`` f32 logits band and a ``_repeat_kv`` copy of the
whole slab every step; this kernel streams per-slot length-masked K/V
blocks straight off the ``(num_slots, max_len, Hkv * D)`` slab with an
online-softmax accumulator — flash-decode, the single-query sibling of
``ops/flash_attention.py``.

Layout and masking:

- The slab is consumed AS THE ENGINE STORES IT, ``(B, max_len,
  Hkv * D)`` with the head tail merged (``serve/kv_cache.py``) — no
  transpose and no reshape of the multi-hundred-MB cache per decode
  step.  Mosaic tiles the last two axes of a block as (sublane, lane)
  and refuses a block that squeezes the second-to-last one, so a head
  cannot be picked by squeezing an ``Hkv`` axis; with the tail merged a
  KV head is the 128-aligned lane range ``[h * D, (h + 1) * D)`` of
  the plain ``(block_k, g * D)`` K/V block.
  The cache has to be STORED that way, because on the chip the merge is
  not a view: XLA tiles the last two axes of an array too, so a bf16
  ``(B, L, Hkv, D)`` array is laid out ``{3,2,1,0:T(8,128)(2,1)}`` with
  one tile holding the ``Hkv`` heads of ONE row, and ``(B, L, Hkv * D)``
  is ``{2,1,0:T(8,128)(2,1)}`` with one tile holding eight ROWS of one
  head's lanes — the ``reshape`` between the two reads and writes the
  whole array (67 MB a slab at Mistral-7B widths, for K and for V, in
  every layer of every decode step: 29 % of the device's busy time when
  this wrapper still did it, PERF.md §6 PR 28).
  Grid is ``(B, Hkv / g, n_k)``: one grid step reads ``block_k`` rows
  of a slot for ``g`` KV heads at once and walks them with static lane
  slices, and ``g`` is all ``Hkv`` wherever the block fits the fast
  memory (grid ``(B, 1, n_k)``).  A grid step costs 0.2-0.35 us on a
  v5e whether it reads anything or not, and most steps of a slab whose
  slots are shallow are pruned ones: cut a head a step (the kernel's
  grid until PR 33) a call at Mistral-7B's widths was 512 steps and took
  190 us with every slot at depth 0, 203 us at the serving cell's depths
  — the time was the grid (PERF.md §6 PR 33).  Whole rows are also one
  contiguous piece of HBM where a head's rows are ``D`` lanes at a
  stride of ``Hkv * D``.  ``g`` and ``block_k`` come from ONE function
  of the shapes, :func:`_blocking`, with its fast-memory budget and
  what it trades written down in it; ``D`` that is not a multiple of
  the 128-lane width (GPT-2's 64, the test models) cannot be cut out of
  the tail by a block, so there ``g`` is ``Hkv`` always.  int8 scales
  are stored ``(B, max_len, Hkv)`` and the kernel selects a head's
  column with an exact one-hot lane reduction.
- GQA is folded in: the ``n_rep = Hq // Hkv`` query heads of one KV
  group ride as the ROWS of each matmul (padded up to the f32 sublane
  minimum of 8), so no repeated K/V ever materializes — the kernel
  analogue of ``_repeat_kv``.
- Per-slot lengths arrive as scalar-prefetched ``positions``: block
  ``kk`` is skipped entirely when ``kk * block_k > positions[b]``
  (block-level pruning — compute scales with the slot's actual depth,
  not ``max_len``), the K/V index map clamps pruned blocks onto the last
  visible one so their DMAs are no-ops, and the diagonal block applies
  the ``j <= positions[b]`` mask elementwise.

``paged_decode_attention`` is the same kernel over the serve engine's
PAGED cache (``serve/kv_cache.py``): K/V live as per-layer page pools
``(num_pages, page_size, Hkv * D)`` and each slot's logical row is the
chain of pages its scalar-prefetched page-table row names.  The K block
is the page — the index map does the gather, the kernel body is shared —
so shared-prefix pages are attended in place, never copied to a
contiguous buffer.

Exactness contract (pinned in tests/test_decode_attention.py): when the
whole row fits one K block (:func:`_blocking` keeps a slab of up to
255 rows, or one page, whole) the kernel computes mask -> rowmax -> exp
-> sum -> divide -> dot in exactly ``jax.nn.softmax``'s op order, so the
interpret-mode PROBABILITIES are bit-identical to
``slot_cached_attention``'s jnp path; the one remaining divergence is the final P@V contraction, whose
reduction XLA's CPU emitter associates differently for the batched
einsum than for any per-(slot, kv-head) dot a blocked kernel can issue —
measured <= 2 f32 ulps, and pinned at that tolerance (the same
exact-math-modulo-association bar ``flash_attention``'s interpret tests
use).  Across multiple K blocks the online-softmax merge additionally
defers normalization (divide after the accumulated dot), the standard
flash trade.  ENGINE-level exactness is stronger: fused K-step decode
vs K one-step dispatches is bit-identical because both route through
this same kernel (tests/test_serve.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _shrink_block

__all__ = [
    "decode_attention",
    "paged_decode_attention",
    "decode_attention_block",
    "paged_decode_attention_block",
]

_NEG_INF = -1e30
_MIN_ROWS = 8  # f32 sublane minimum: GQA group rows pad up to this
_LANES = 128  # TPU lane width: a head is whole lane tiles iff D % 128 == 0
# What one call's blocks may take of the fast memory a kernel gets
# without asking for more (16 MiB of scoped VMEM on a v5e; the rest is
# the compiler's own).
_VMEM_BUDGET = 12 * 2**20
# A grid step costs 0.2-0.35 us on a v5e whether it reads anything or
# not; a block costs its rows read past a slot's depth (half of it on
# average) and the DMA of a slot's first block and the arithmetic of its
# last, which nothing overlaps.  Taken together a step costs what about
# this many bytes of a block cost (PERF.md §6 PR 33: the sweep of
# ``scripts/bench_decode_attention.py`` over 4, 8 and 16 KiB of K and V
# a row and 2048 and 8192 rows a slot puts it between 64 and 256 KiB).
_STEP_BYTES = 128 * 2**10
_MIN_BLOCK_K = 128  # the MXU's width: a slab block is not cut below it


def _vmem_bytes(
    g: int, block_k: int, hkv: int, d: int, itemsize: int, rows: int
) -> int:
    """Fast memory of one grid step that reads ``block_k`` cache rows of
    ``g`` KV heads: what Pallas double-buffers, the scratch, and the
    body's float32 temporaries."""
    up = lambda n: -(-n // _LANES) * _LANES  # a minor axis pads to lanes
    kv = 2 * 2 * block_k * up(g * d) * itemsize  # K and V, two buffers
    # the int8 cache's (block_k, Hkv) f32 scale blocks, counted for every
    # cache so that one rule serves both
    scales = 2 * 2 * block_k * up(hkv) * 4
    # q and o (two buffers each) and acc, as f32; m and l one lane wide
    small = (5 * up(d) + 2 * _LANES) * g * rows * 4
    # every head's K and V slices in f32, its logits and probabilities
    temporaries = (2 * up(d) + 2 * rows) * g * block_k * 4
    return kv + scales + small + temporaries


def _blocking(
    hkv: int, d: int, itemsize: int, kv_rows: int, rows: int,
    block_k: int, min_block_k: int = _MIN_BLOCK_K,
) -> tuple[int, int]:
    """``(g, block_k)``: how a call is cut into grid steps, from its
    shapes alone — ``Hkv`` KV heads of ``d`` lanes, a cache of
    ``itemsize`` bytes an element and ``kv_rows`` logical rows a slot,
    ``rows`` query rows a KV head (``S * n_rep``, padded).  A slot takes
    ``(Hkv / g) * (kv_rows / block_k)`` grid steps, pruned ones included.

    ``g``: a step reads its rows for as many heads as fit, all ``Hkv``
    if it can — whole cache rows, one contiguous piece of HBM, an eighth
    of the steps at Mistral's widths — and else the largest divisor of
    ``Hkv`` whose blocks are inside ``_VMEM_BUDGET``.  A head that is not
    whole lane tiles (``d % 128 != 0``) cannot be cut out of the tail by
    a block, so there ``g`` is ``Hkv``.

    ``block_k``: the caller's upper bound, halved until it divides
    ``kv_rows``, then halved while that is cheaper by ``_STEP_BYTES`` —
    half the rows past a slot's depth for twice the steps — or while the
    whole-row block is over the budget (the same count of steps as fewer
    heads would give, and less read past the depth), but not below
    ``min_block_k``."""
    block_k = _shrink_block(block_k, kv_rows)
    row_bytes = 2 * hkv * d * itemsize  # K and V

    def fits(g, block_k):
        return _vmem_bytes(g, block_k, hkv, d, itemsize, rows) <= _VMEM_BUDGET

    # a slot pays ``bk * row_bytes`` for its blocks and ``_STEP_BYTES``
    # for each of its ``kv_rows / bk`` steps; ``bk / 2`` is cheaper iff
    # ``bk / 2 * bk * row_bytes > kv_rows * _STEP_BYTES``
    while (
        block_k % 2 == 0
        and block_k // 2 >= min_block_k
        and (
            (block_k // 2) * block_k * row_bytes > kv_rows * _STEP_BYTES
            or not fits(hkv, block_k)
        )
    ):
        block_k //= 2
    if d % _LANES != 0:
        return hkv, block_k
    return next(
        g for g in range(hkv, 0, -1)
        if hkv % g == 0 and (g == 1 or fits(g, block_k))
    ), block_k


def _decode_kernel(
    *refs,  # scalar prefetch: (B,) int32 per-slot BASE depth [, page table],
    #         q (g, rows, D), k/v (block_k, g * D) [, k/v scales
    #         (block_k, Hkv)], o (g, rows, D), then VMEM scratch
    #         acc (g, rows, D), m/l (g, rows, 1)
    n_prefetch: int,
    scale: float,
    block_k: int,
    n_k: int,
    s: int,
    n_rep: int,
    g: int,
    quantized: bool,
):
    """One kernel body for all four families.  ``S`` query tokens per
    slot ride as EXTRA MATMUL ROWS — row ``r`` is query token
    ``r // n_rep`` of GQA head ``r % n_rep``, masked to its OWN depth
    ``pos + r // n_rep`` (the kernel analogue of ``_slot_attend_block``'s
    shifted mask; ``S == 1`` is the plain decode step and every row masks
    at ``pos``).  The paged families differ only in their K/V index maps
    (which block to DMA): the in-block math is position-indexed exactly
    as in the contiguous layout, so slab and paged cannot diverge.  ``g``
    KV heads share one K/V block (module docstring); each is an
    independent attention over its own lane slice."""
    pos_ref = refs[0]
    refs = refs[n_prefetch:]
    if quantized:
        q_ref, k_ref, v_ref, ks_ref, vs_ref = refs[:5]
        o_ref, acc_ref, m_ref, l_ref = refs[5:]
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    d = q_ref.shape[-1]
    b = pl.program_id(0)
    hb = pl.program_id(1)  # read here: not lowerable inside a pl.when
    kk = pl.program_id(2)
    pos = pos_ref[b]

    def kv_block(x_ref, sc_ref, j):
        """Head ``j``'s (block_k, D) rows of this K/V block in f32.

        An int8 block dequantizes HERE — elementwise ``int8 -> f32 *
        scale`` on the block already resident in VMEM, the exact ops the
        jnp reference's ``dequantize_kv`` applies, so quantized
        kernel-vs-jnp parity inherits the unquantized bounds.  The
        head's scale column comes out of the (block_k, Hkv) scale block
        by a one-hot lane reduction: one real term plus zeros, exact."""
        x = x_ref[...] if g == 1 else x_ref[:, j * d:(j + 1) * d]
        x = x.astype(jnp.float32)
        if sc_ref is not None:
            sc = sc_ref[...]
            lane = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            x = x * jnp.sum(
                jnp.where(lane == hb * g + j, sc, 0.0),
                axis=-1, keepdims=True,
            )
        return x

    def tile(j):
        """Masked (rows, block_k) f32 logits of head ``j`` for this K
        block."""
        logits = (
            jax.lax.dot_general(
                q_ref[j].astype(jnp.float32), kv_block(k_ref, ks_ref, j),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        cols = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1
        )
        depth = pos
        if s > 1:
            # padded rows (row // n_rep >= s) mask like the last real
            # token; their outputs are sliced off by the wrapper
            row = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
            depth = pos + jnp.minimum(row // n_rep, s - 1)
        return jnp.where(cols <= depth, logits, _NEG_INF)

    def pv(p, j):
        return jax.lax.dot_general(
            p, kv_block(v_ref, vs_ref, j),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # The heads of a block are independent, and each is a chain of two
    # small matmuls with two cross-lane reductions between them.  Written
    # head after head the chip runs the chains one after the other (0.26
    # us a head and block on a v5e, PERF.md §6 PR 33); written stage by
    # stage over the heads, as below, they overlap.  A head's operations
    # and their order are the same either way.
    heads = range(g)

    if n_k == 1:
        # Single-block fast path in the jnp reference's exact op order
        # (mask, rowmax, exp, sum, divide, dot) — bit-identical to
        # slot_cached_attention's softmax in interpret mode.  No scratch
        # state: the whole visible row is here.
        logits = [tile(j) for j in heads]
        m = [jnp.max(x, axis=-1, keepdims=True) for x in logits]
        unnorm = [jnp.exp(x - mj) for x, mj in zip(logits, m)]
        probs = [u / jnp.sum(u, axis=-1, keepdims=True) for u in unnorm]
        for j in heads:
            o_ref[j] = pv(probs[j], j).astype(o_ref.dtype)
        return

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level pruning on the DEEPEST query row (pos + s - 1): blocks
    # entirely past it are skipped (their DMA is also clamped away by
    # the index map)
    @pl.when(kk * block_k <= pos + (s - 1))
    def _compute():
        logits = [tile(j) for j in heads]
        m_prev = [m_ref[j] for j in heads]
        m_new = [
            jnp.maximum(mp, jnp.max(x, axis=-1, keepdims=True))
            for mp, x in zip(m_prev, logits)
        ]
        p = [jnp.exp(x - mn) for x, mn in zip(logits, m_new)]
        correction = [jnp.exp(mp - mn) for mp, mn in zip(m_prev, m_new)]
        out = [pv(p[j], j) for j in heads]
        for j in heads:
            l_ref[j] = l_ref[j] * correction[j] + jnp.sum(
                p[j], axis=-1, keepdims=True
            )
            acc_ref[j] = acc_ref[j] * correction[j] + out[j]
            m_ref[j] = m_new[j]

    @pl.when(kk == n_k - 1)
    def _emit():
        # column 0 is always visible (pos >= 0), so l > 0; the guard only
        # covers pathological all-underflow rows, matching _kernel
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


# One traced and lowered computation for all the layers of a program: a
# decode program calls this once a layer with the same shapes, and traced
# in place each call costs ~60 ms of a process's set-up on the host (the
# body is unrolled over the heads; 24 layers, twice a program: +2.7 s of
# ``setup_s`` on the Mistral cell, PERF.md §6 PR 33).  As a jitted
# function it is traced on its first call and found in jit's cache after.
# The scope inside keeps a transformation's wrapping (``vmap(...)``) off
# the kernel's own name (ops/flash_attention.py has the long form).
@functools.partial(jax.jit, static_argnames=("block_k", "scale", "interpret"))
@jax.named_scope("decode_attention")
def _launch(
    q, ck, cv, k_scale, v_scale, positions, page_tables, *,
    block_k, scale, interpret,
):
    """Shared wrapper of the four families.  ``ck``/``cv``: the slab
    (B, max_len, Hkv * D) or, with ``page_tables`` (B, pages_per_slot),
    the pools (num_pages, page_size, Hkv * D), handed to the kernel as
    they are; ``Hkv`` is ``ck.shape[-1] // D``.  ``positions`` (per-slot
    base depths) and the flattened table are the scalar-prefetch
    operands.  :func:`_blocking` cuts a slot's logical rows into the
    blocks of a grid step, ``block_k`` its upper bound (the paged
    callers pass the page)."""
    b, s, hq, d = q.shape
    if ck.ndim != 3 or ck.shape[-1] % d != 0 or cv.shape != ck.shape:
        raise ValueError(
            f"K/V cache shapes {ck.shape}/{cv.shape} are not the stored "
            f"layout (lead, rows, Hkv * {d})"
        )
    hkv = ck.shape[-1] // d
    if hq % hkv != 0:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    quantized = k_scale is not None
    if quantized:
        want = ck.shape[:2] + (hkv,)
        if k_scale.shape != want or v_scale.shape != want:
            raise ValueError(
                f"kv scale shapes {k_scale.shape}/{v_scale.shape} != "
                f"cache rows + kv heads {want}"
            )
    n_rep = hq // hkv

    # fold (B, S, Hq, D) into (B, Hkv, rows, D): S tokens x n_rep GQA
    # heads per KV group, padded up to the f32 sublane minimum
    real = s * n_rep
    rows = -(-real // _MIN_ROWS) * _MIN_ROWS
    # the paged block is the page: there only the heads a step reads give
    paged = page_tables is not None
    ps = ck.shape[1]
    kv_rows = ps * page_tables.shape[1] if paged else ps
    g, block_k = _blocking(
        hkv, d, ck.dtype.itemsize, kv_rows, rows, block_k,
        ps if paged else _MIN_BLOCK_K,
    )
    n_k = kv_rows // block_k
    # the last visible block of slot ``bb``, by its deepest query row
    if paged:
        # the table is flattened for SMEM scalar prefetch: entry b*n_k + kk
        prefetch = [positions, page_tables.astype(jnp.int32).reshape(-1)]

        def kv_index(bb, kk, pos_ref, pt_ref):
            last = jnp.minimum(pos_ref[bb] + (s - 1), kv_rows - 1) // ps
            return (pt_ref[bb * n_k + jnp.minimum(kk, last)], 0)

    else:
        prefetch = [positions]

        def kv_index(bb, kk, pos_ref):
            last = jnp.minimum(pos_ref[bb] + (s - 1), kv_rows - 1) // block_k
            return (bb, jnp.minimum(kk, last))

    qg = q.reshape(b, s, hkv, n_rep, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, hkv, real, d)
    if rows != real:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - real), (0, 0)))

    def q_index(bb, h, kk, *_):
        return (bb, h, 0, 0)

    def data_index(bb, h, kk, *pf):
        return (*kv_index(bb, kk, *pf), h)

    def scale_index(bb, h, kk, *pf):
        return (*kv_index(bb, kk, *pf), 0)

    in_specs = [
        pl.BlockSpec((None, g, rows, d), q_index),
        pl.BlockSpec((None, block_k, g * d), data_index),
        pl.BlockSpec((None, block_k, g * d), data_index),
    ]
    operands = [qg, ck, cv]
    if quantized:
        in_specs += [pl.BlockSpec((None, block_k, hkv), scale_index)] * 2
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, hkv // g, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, g, rows, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((g, rows, d), jnp.float32),
            pltpu.VMEM((g, rows, 1), jnp.float32),
            pltpu.VMEM((g, rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, n_prefetch=len(prefetch), scale=(
                scale if scale is not None else 1.0 / math.sqrt(d)
            ),
            block_k=block_k, n_k=n_k, s=s, n_rep=n_rep, g=g,
            quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        # the kernel's fixed name in the compiled program and in a profile
        name="tdx_paged_decode_attention" if paged else "tdx_decode_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*prefetch, *operands)
    return (
        out[:, :, :real, :]
        .reshape(b, hkv, s, n_rep, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, s, hq, d)
    )


def _interpret(interpret: Optional[bool]) -> bool:
    """Off-TPU the kernels run in interpret mode, per the repo kernel
    convention; resolved before the jitted wrapper, whose cache it keys."""
    if interpret is None:
        return jax.devices()[0].platform != "tpu"
    return interpret


def decode_attention(
    q: jax.Array,
    ck: jax.Array,
    cv: jax.Array,
    positions: jax.Array,
    *,
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Slot-paged single-token decode attention (post-write).

    ``q``: (B, 1, Hq, D) — each slot's next-token query, positional
    encoding already applied.  ``ck``/``cv``: the engine slab
    (B, max_len, Hkv * D) with the new K/V already written at each slot's
    row (``slot_cached_attention`` performs the write; this kernel only
    attends).  ``positions``: (B,) int32 — slot ``b`` attends cache rows
    ``j <= positions[b]``.  Returns (B, 1, Hq, D) in ``q.dtype``.

    How the call is cut into grid steps — the rows a step reads and
    for how many KV heads at once — is :func:`_blocking`'s to decide
    from the shapes; ``block_k`` is the upper bound on the rows it may
    pick (it halves the bound until it divides ``max_len``, and further
    where a shorter block is cheaper or the block would not fit the
    fast memory).  When one block covers ``max_len`` the interpret-mode
    result is bit-identical to the jnp reference (module docstring).
    ``interpret`` defaults to True off-TPU, per the repo kernel
    convention.

    **int8 cache** (``kv_dtype="int8"``): pass the f32 per-row per-head
    scales as ``k_scale``/``v_scale`` of shape (B, max_len, Hkv) —
    they ride the SAME row-block index as their data (one
    (block_k, Hkv) scale block per K/V block, clamped together), and
    the kernel dequantizes each block in VMEM before Q·K / P·V, which
    stay f32.  HBM traffic per step is the int8 block plus a 1/D-sized
    scale column — the halved-bytes contract the cost cards price.
    """
    if q.shape[1] != 1:
        raise ValueError(
            f"decode_attention takes one token per slot, got S={q.shape[1]}"
        )
    return decode_attention_block(
        q, ck, cv, positions, scale=scale, block_k=block_k,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
    )


def decode_attention_block(
    q: jax.Array,
    ck: jax.Array,
    cv: jax.Array,
    positions: jax.Array,
    *,
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Slot-paged MULTI-token decode attention (post-write): the
    speculative verify block.  ``q``: (B, S, Hq, D) — ``S = K + 1``
    candidate tokens per slot, query ``(b, i)`` masked to cache rows
    ``j <= positions[b] + i``.  ``ck``/``cv``: the engine slab with all
    S candidate K/V rows already scattered
    (``serve/kv_cache.scatter_slot_tokens``).  Returns (B, S, Hq, D).

    The S tokens fold into the GQA row axis (``rows = S * n_rep`` padded
    to the sublane minimum), so the verify costs ONE kernel launch with
    a slightly taller matmul instead of S launches — the whole point of
    speculation.  The DMA clamp and block pruning use the block's
    deepest row ``positions[b] + S - 1`` (blocks past it re-map onto the
    last visible one: Pallas skips the DMA when the mapped block index
    is unchanged, so pruned grid steps move no bytes).  ``block_k`` is
    the upper bound it is in :func:`decode_attention`: the rows a step
    reads and the heads it reads them for are :func:`_blocking`'s, and
    the taller matmul's ``rows`` are one of its arguments.
    ``k_scale``/``v_scale``: int8-cache dequant scales, exactly as in
    :func:`decode_attention`.
    """
    return _launch(
        q, ck, cv, k_scale, v_scale, positions.astype(jnp.int32), None,
        block_k=block_k, scale=scale, interpret=_interpret(interpret),
    )


def paged_decode_attention(
    q: jax.Array,
    ck: jax.Array,
    cv: jax.Array,
    page_tables: jax.Array,
    positions: jax.Array,
    *,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Paged single-token decode attention (post-write): the serving
    engine's prefix-sharing sibling of :func:`decode_attention`.

    ``q``: (B, 1, Hq, D).  ``ck``/``cv``: the per-layer page pools,
    shape (num_pages, page_size, Hkv * D), the new K/V already scattered
    at each slot's current row (``slot_cached_attention`` performs the
    write).  ``page_tables``: (B, pages_per_slot) int32 — slot ``b``'s
    logical cache is the concatenation of the pages ``page_tables[b]``
    names.  ``positions``: (B,) int32 visible depths as in the slot
    kernel.  Returns (B, 1, Hq, D) in ``q.dtype``.

    The K block IS the page (``block_k == page_size``): the grid's K/V
    index map reads the scalar-prefetched page table to pick which pool
    page to DMA — K/V are gathered page-by-page straight off the pool,
    never copied into a contiguous buffer.  Block pruning and the
    DMA-clamp work as in the slot kernel, but in TABLE space: blocks
    past ``positions[b] // page_size`` re-map onto the slot's last
    visible page.  When one page covers the whole logical row
    (``pages_per_slot == 1``) the kernel takes the same
    bit-exact-softmax fast path the slot kernel pins; multi-page rows
    take the online-softmax merge at the same <= 2-ulp association bar
    (tests/test_decode_attention.py).  ``k_scale``/``v_scale``:
    int8-cache dequant scales of shape (num_pages, page_size, Hkv),
    gathered through the same table as their pages.
    """
    if q.shape[1] != 1:
        raise ValueError(
            f"paged_decode_attention takes one token per slot, "
            f"got S={q.shape[1]}"
        )
    return paged_decode_attention_block(
        q, ck, cv, page_tables, positions, scale=scale,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
    )


def paged_decode_attention_block(
    q: jax.Array,
    ck: jax.Array,
    cv: jax.Array,
    page_tables: jax.Array,
    positions: jax.Array,
    *,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Paged multi-token decode attention: :func:`decode_attention_block`
    over the page pools, gathered page-by-page through the
    scalar-prefetched table exactly like :func:`paged_decode_attention`
    (block == page; pruning and the DMA clamp run in TABLE space on the
    block's deepest row ``positions[b] + S - 1``).  ``k_scale``/
    ``v_scale``: int8-cache dequant scales of shape (num_pages,
    page_size, Hkv), gathered through the same table."""
    if page_tables.shape[0] != q.shape[0]:
        raise ValueError(
            f"page_tables rows {page_tables.shape[0]} != batch {q.shape[0]}"
        )
    return _launch(
        q, ck, cv, k_scale, v_scale, positions.astype(jnp.int32),
        page_tables, block_k=ck.shape[1], scale=scale,
        interpret=_interpret(interpret),
    )
