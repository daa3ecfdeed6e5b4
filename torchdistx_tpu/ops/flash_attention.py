"""Pallas flash attention for TPU.

True flash schedule: the grid streams K/V blocks (innermost, sequential)
against each Q block with an online-softmax accumulator in VMEM scratch —
neither the (S x S) logits matrix nor the full K/V ever sit in VMEM, so
context length is bounded by HBM, not VMEM, and HBM traffic stays O(S*D).
Matmuls are MXU-shaped (block_q x d x block_k).

GQA is handled in the BlockSpec index maps: K/V are laid out per KV head
and each query head's programs map onto their group's KV blocks — no
repeated K/V in HBM.

The causal mask is end-aligned like ``multihead_attention`` (query i may
see keys up to ``skv - sq + i``), so the two agree for every (Sq, Skv)
combination, including cached decode where Sq < Skv.

Precision: every matmul takes its operands in the dtype the rows are
stored in and accumulates in float32; everything between the matmuls
(logits, mask, max, sum, exponentials, lse, delta, the accumulators) is
float32.  (On a v5e, Mosaic gives float32 operands at the default
precision the same single bf16 pass: bf16 rows cast up first gave the
same bits in the same time, PR 40.  The tiles' size is what costs.)
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "rel_pos_bucket"]

_NEG_INF = -1e30
_RES_LANES = 128  # TPU lane width: residual (m, l) rows broadcast over it

# Upper bounds of a tile's (query rows, key rows) where the caller names
# none.  The kernels are bound by their MXU passes and what each grid
# step costs around them, not by the masked work a larger causal tile
# adds: on a v5e (scripts/bench_flash_attention.py --cells, bf16 rows,
# microseconds a call, PR 40) 4 x 2048 tokens at 16 heads of 128 took
#   forward 1824 / dK dV 1854 / dQ 1664   at 256 x 512,
#           1416 /       1483 /    1231   at 512 x 512,
#           1056 /       1350 /    1173   at 512 x 1024,
#            922 /       1345 /    1055   at 1024 x 1024,
# and a 6144-token prefill at 32 heads of 192 / 128 8759 -> 4212.  A
# dK/dV tile of 1024 x 2048 does not fit the kernel's 16 MiB of VMEM,
# and with float32 rows 1024 x 1024 passes it by 0.75 MB; with a bias
# the (query, key) tile of it and of dbias rides along at 2-4 bytes an
# element.  Those keep the bound they have always been compiled with.
_BLOCKS = (1024, 1024)  # rows of two bytes or fewer, no bias
_BLOCKS_SMALL = (256, 512)


def rel_pos_bucket(rel_pos, *, bidirectional: bool, buckets: int, max_dist: int):
    """T5's relative-position bucketing (log-spaced beyond buckets/2).

    Pure jnp on any integer array — shared by the T5 model (host-side
    bias materialization) and the flash kernels' in-kernel bucket-bias
    tiles, so the two bias sources can never diverge."""
    ret = 0
    n = -rel_pos
    if bidirectional:
        buckets = buckets // 2
        ret = jnp.where(n < 0, buckets, 0)
        n = jnp.abs(n)
    else:
        n = jnp.maximum(n, 0)
    max_exact = buckets // 2
    is_small = n < max_exact
    log_big = max_exact + (
        jnp.log(n.astype(jnp.float32) / max_exact + 1e-6)
        / jnp.log(max_dist / max_exact)
        * (buckets - max_exact)
    ).astype(jnp.int32)
    log_big = jnp.minimum(log_big, buckets - 1)
    return ret + jnp.where(is_small, n, log_big)


def _bucket_bias_tile(table_ref, qi, ki, *, block_q, block_k, bucket_cfg):
    """(block_q, block_k) f32 bias tile computed IN-KERNEL from the
    per-head bucket table (``table_ref``: (1, buckets) VMEM block).

    The bucket ids come from the tile's global (row, col) offsets; the
    table lookup is a static loop of ``buckets`` selects against scalar
    reads — VPU work linear in the tile size, no (H, S, S) bias in HBM.
    Requires sq == skv (training shapes): bucket positions are
    start-aligned."""
    buckets, max_dist, bidirectional = bucket_cfg
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    bucket = rel_pos_bucket(
        cols - rows,
        bidirectional=bidirectional,
        buckets=buckets,
        max_dist=max_dist,
    )
    bias = jnp.zeros((block_q, block_k), jnp.float32)
    for b in range(buckets):  # static, small (32 for T5)
        bias = bias + jnp.where(
            bucket == b, table_ref[0, b].astype(jnp.float32), 0.0
        )
    return bias


def _block_visible(qi, kk, *, block_q, block_k, diag_offset, causal, window):
    """Block-level pruning predicate shared by all kernels: skip K blocks
    entirely above the causal diagonal AND (with a sliding window)
    entirely below the attention band ``cols > rows - window``."""
    vis = jnp.ones((), bool)
    if causal:
        vis = vis & (
            kk * block_k <= qi * block_q + block_q - 1 + diag_offset
        )
    if window is not None:
        vis = vis & (
            kk * block_k + block_k - 1
            >= qi * block_q + diag_offset - (window - 1)
        )
    return vis


def _tile_mask(qi, kk, shape, *, block_q, block_k, diag_offset, causal,
               window):
    """(block_q, block_k) bool visibility tile: causal upper mask and the
    sliding-window lower bound (query i sees keys (i-window, i])."""
    rows = (
        qi * block_q
        + diag_offset
        + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    )
    cols = kk * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = jnp.ones(shape, bool)
    if causal:
        mask = mask & (cols <= rows)
    if window is not None:
        mask = mask & (cols > rows - window)
    return mask


def _shrink_block(block: int, s: int) -> int:
    """Halve ``block`` until it divides ``s`` (upper-bound semantics shared
    by the forward and both backwards — one policy, one place)."""
    block = min(block, s)
    while block > 1 and s % block != 0:
        block //= 2
    return block


def _dot(a, b, contract):
    """``a`` x ``b`` over ``contract`` on the MXU, float32 out.  The
    operands go in as they are stored; a float32 tile that meets stored
    rows is rounded to their dtype by the caller, at the call.  Rows of
    two dtypes meet in the wider one."""
    dtype = jnp.promote_types(a.dtype, b.dtype)
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (contract, ((), ())),
        preferred_element_type=jnp.float32,
    )


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    *rest,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    n_k: int,
    diag_offset: int,
    has_bias: bool,
    emit_residuals: bool = False,
    emit_lse: bool = False,
    bucket_cfg=None,
    window=None,
):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    o_ref = rest.pop(0)
    m_out_ref = rest.pop(0) if emit_residuals else None
    l_out_ref = rest.pop(0) if emit_residuals else None
    lse_out_ref = rest.pop(0) if emit_lse else None
    acc_ref, m_ref, l_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # block-level pruning: skip K blocks fully outside the causal /
    # sliding-window band for every row of this Q block
    any_visible = _block_visible(
        qi, ki, block_q=block_q, block_k=block_k,
        diag_offset=diag_offset, causal=causal, window=window,
    )

    @pl.when(any_visible)
    def _compute():
        q = q_ref[0]  # (block_q, d)
        k = k_ref[0]  # (block_k, d)
        v = v_ref[0]
        logits = _dot(q, k, ((1,), (1,))) * scale  # (block_q, block_k)
        if has_bias:
            if bucket_cfg is not None:
                logits = logits + _bucket_bias_tile(
                    bias_ref, qi, ki,
                    block_q=block_q, block_k=block_k,
                    bucket_cfg=bucket_cfg,
                )
            else:
                logits = logits + bias_ref[0].astype(jnp.float32)
        if causal or window is not None:
            mask = _tile_mask(
                qi, ki, logits.shape, block_q=block_q, block_k=block_k,
                diag_offset=diag_offset, causal=causal, window=window,
            )
            logits = jnp.where(mask, logits, _NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * correction + jnp.sum(p, axis=-1, keepdims=True)
        # l sums the float32 p; P.V takes p in the values' dtype
        acc_ref[:] = acc_ref[:] * correction + _dot(
            p.astype(v.dtype), v, ((1,), (0,))
        )
        m_ref[:] = m_new

    @pl.when(ki == n_k - 1)
    def _emit():
        if emit_residuals:
            # ring consumers re-scale and re-normalize across blocks:
            # emit the RAW f32 accumulator (no divide, no output-dtype
            # rounding — the cross-block combine stays pure f32)
            o_ref[0] = acc_ref[:].astype(o_ref.dtype)
        else:
            o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(
                o_ref.dtype
            )
        if emit_residuals:
            # per-row online-softmax state, consumed by ring attention's
            # cross-block combine: m = running max, l = sum of
            # exp(logits - m).  Stored broadcast across a 128-lane
            # trailing dim (Mosaic requires (8, 128)-divisible or whole-
            # array trailing block dims — the same layout jax's own TPU
            # flash kernel uses for its lse output); callers read lane 0.
            m_out_ref[...] = jnp.broadcast_to(m_ref[:], m_out_ref.shape)
            l_out_ref[...] = jnp.broadcast_to(l_ref[:], l_out_ref.shape)
        if emit_lse:
            # log-sum-exp per row, consumed by the pallas backward: it
            # reconstitutes probabilities as exp(logits - lse) without an
            # online max.  Same broadcast-lane layout as the residuals.
            lse = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))
            lse_out_ref[...] = jnp.broadcast_to(lse, lse_out_ref.shape)


def _bwd_recompute(
    q_ref, do_ref, o_ref, lse_ref, k_ref, v_ref, bias_ref, *,
    scale, causal, block_q, block_k, qi, kk, diag_offset,
    bucket_cfg=None, window=None,
):
    """Shared backward-body recompute: reconstitute this tile's
    probabilities from the saved lse and form the dS ingredients.

    Returns ``(p, dp, delta)`` with ``p`` causal-masked:
    ``dS = p * (dp - delta) * scale`` (dq/dk) and
    ``dbias = p * (dp - delta)`` (bias enters logits unscaled).  One body
    for all three backward kernels so a masking/p-reconstruction fix can
    never desynchronize them; ``qi``/``kk`` are the tile's Q/K block
    indices in whatever grid order the caller uses."""
    q = q_ref[0]  # (block_q, d)
    k = k_ref[0]  # (block_k, d)
    v = v_ref[0]
    do = do_ref[0]  # (block_q, d)
    o = o_ref[0]
    lse = lse_ref[...][:, :1]  # (block_q, 1)
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32),
        axis=-1, keepdims=True,
    )
    logits = _dot(q, k, ((1,), (1,))) * scale  # (block_q, block_k)
    if bias_ref is not None:
        if bucket_cfg is not None:
            logits = logits + _bucket_bias_tile(
                bias_ref, qi, kk,
                block_q=block_q, block_k=block_k, bucket_cfg=bucket_cfg,
            )
        else:
            logits = logits + bias_ref[0].astype(jnp.float32)
    p = jnp.exp(logits - lse)
    if causal or window is not None:
        mask = _tile_mask(
            qi, kk, p.shape, block_q=block_q, block_k=block_k,
            diag_offset=diag_offset, causal=causal, window=window,
        )
        p = jnp.where(mask, p, 0.0)
    dp = _dot(do, v, ((1,), (1,)))
    return p, dp, delta


def _bwd_dkv_kernel(
    q_ref,
    do_ref,
    o_ref,
    lse_ref,
    k_ref,
    v_ref,
    *rest,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    n_q: int,
    diag_offset: int,
    has_bias: bool = False,
    bucket_cfg=None,
    window=None,
):
    """Grid (b*hq, n_k, n_q): each program owns one K/V block and streams
    Q blocks (innermost, sequential), accumulating dK/dV in VMEM —
    FlashAttention-2 backward, K/V-stationary half.

    ``delta = rowsum(dO * O)`` is computed IN-kernel from the O block (a
    cheap VPU rowsum) rather than precomputed: an O block is half the HBM
    bytes of a 128-lane-broadcast f32 delta block, and nothing gets
    materialized.  (Only lse still needs the broadcast-lane input
    layout: 1D-row-block and trailing-1 layouts have not been shown to
    compile — ask the compiler (tests/test_chip_compile.py) before
    assuming Mosaic accepts them.)

    With ``has_bias`` the logits recompute adds the streamed bias block —
    the saved lse already includes it, so p comes out exact."""
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    kk = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    any_visible = _block_visible(
        qi, kk, block_q=block_q, block_k=block_k,
        diag_offset=diag_offset, causal=causal, window=window,
    )

    @pl.when(any_visible)
    def _compute():
        p, dp, delta = _bwd_recompute(
            q_ref, do_ref, o_ref, lse_ref, k_ref, v_ref, bias_ref,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            qi=qi, kk=kk, diag_offset=diag_offset, bucket_cfg=bucket_cfg,
            window=window,
        )
        # dV += P^T dO
        dv_acc[:] = dv_acc[:] + _dot(
            p.astype(do_ref.dtype), do_ref[0], ((0,), (0,))
        )
        # dS = P * (dO V^T - delta) * scale;  dK += dS^T Q
        ds = p * (dp - delta) * scale
        dk_acc[:] = dk_acc[:] + _dot(
            ds.astype(q_ref.dtype), q_ref[0], ((0,), (0,))
        )

    @pl.when(qi == n_q - 1)
    def _emit():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref,
    do_ref,
    o_ref,
    lse_ref,
    k_ref,
    v_ref,
    *rest,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    n_k: int,
    diag_offset: int,
    has_bias: bool = False,
    bucket_cfg=None,
    window=None,
):
    """Grid (b*hq, n_q, n_k): each program owns one Q block and streams
    K/V blocks — Q-stationary half, same schedule as the forward.
    ``delta`` in-kernel as in ``_bwd_dkv_kernel``."""
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    dq_ref, dq_acc = rest
    qi = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    any_visible = _block_visible(
        qi, kk, block_q=block_q, block_k=block_k,
        diag_offset=diag_offset, causal=causal, window=window,
    )

    @pl.when(any_visible)
    def _compute():
        p, dp, delta = _bwd_recompute(
            q_ref, do_ref, o_ref, lse_ref, k_ref, v_ref, bias_ref,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            qi=qi, kk=kk, diag_offset=diag_offset, bucket_cfg=bucket_cfg,
            window=window,
        )
        ds = p * (dp - delta) * scale
        dq_acc[:] = dq_acc[:] + _dot(
            ds.astype(k_ref.dtype), k_ref[0], ((1,), (0,))
        )

    @pl.when(kk == n_k - 1)
    def _emit():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dbias_kernel(
    q_ref,
    do_ref,
    o_ref,
    lse_ref,
    k_ref,
    v_ref,
    bias_ref,
    db_ref,
    db_acc,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    n_b: int,
    diag_offset: int,
):
    """Grid (hq, n_q, n_k, B) — batch INNERMOST: each program owns one
    (head, q-block, k-block) tile of dbias and streams the batch,
    accumulating ``dS/scale = P * (dO V^T - delta)`` (the logit-space
    gradient; bias enters logits unscaled, so no ``* scale``) in VMEM.
    Consecutive batch steps revisit the same output block, which keeps the
    tile resident until the emit at b == B-1.  dbias is batch-shared like
    the bias itself (T5 relative position bias)."""
    qi = pl.program_id(1)
    kk = pl.program_id(2)
    bb = pl.program_id(3)

    @pl.when(bb == 0)
    def _init():
        db_acc[:] = jnp.zeros_like(db_acc)

    if causal:
        any_visible = kk * block_k <= (
            qi * block_q + block_q - 1 + diag_offset
        )
    else:
        any_visible = jnp.ones((), bool)

    @pl.when(any_visible)
    def _compute():
        p, dp, delta = _bwd_recompute(
            q_ref, do_ref, o_ref, lse_ref, k_ref, v_ref, bias_ref,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            qi=qi, kk=kk, diag_offset=diag_offset,
        )
        db_acc[:] = db_acc[:] + p * (dp - delta)

    @pl.when(bb == n_b - 1)
    def _emit():
        db_ref[0] = db_acc[:].astype(db_ref.dtype)


def _bwd_dtable_kernel(
    q_ref,
    do_ref,
    o_ref,
    lse_ref,
    k_ref,
    v_ref,
    table_ref,
    dt_ref,
    dt_acc,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    n_q: int,
    n_k: int,
    n_b: int,
    diag_offset: int,
    bucket_cfg,
):
    """Bucket-table gradient: grid (hq, n_q, n_k, B) with every non-head
    dimension inner, so one (1, buckets) output tile per head is revisited
    across all (q-block, k-block, batch) steps and the whole reduction
    ``dtable[b] = sum over positions in bucket b of dS/scale`` happens in
    VMEM.  The bucket ids are recomputed per tile exactly as the forward
    did (``_bucket_bias_tile``'s math), so gradient routing can't drift
    from the bias it differentiates."""
    qi = pl.program_id(1)
    kk = pl.program_id(2)
    bb = pl.program_id(3)
    buckets, max_dist, bidirectional = bucket_cfg

    @pl.when((qi == 0) & (kk == 0) & (bb == 0))
    def _init():
        dt_acc[:] = jnp.zeros_like(dt_acc)

    if causal:
        any_visible = kk * block_k <= (
            qi * block_q + block_q - 1 + diag_offset
        )
    else:
        any_visible = jnp.ones((), bool)

    @pl.when(any_visible)
    def _compute():
        p, dp, delta = _bwd_recompute(
            q_ref, do_ref, o_ref, lse_ref, k_ref, v_ref, table_ref,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            qi=qi, kk=kk, diag_offset=diag_offset, bucket_cfg=bucket_cfg,
        )
        ds = p * (dp - delta)  # logit-space grad; bias enters unscaled
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, ds.shape, 0
        )
        cols = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, ds.shape, 1
        )
        bucket = rel_pos_bucket(
            cols - rows,
            bidirectional=bidirectional,
            buckets=buckets,
            max_dist=max_dist,
        )
        for b in range(buckets):  # static, small
            dt_acc[0, b] = dt_acc[0, b] + jnp.sum(
                jnp.where(bucket == b, ds, 0.0)
            )

    @pl.when((qi == n_q - 1) & (kk == n_k - 1) & (bb == n_b - 1))
    def _emit():
        dt_ref[0, :] = dt_acc[0, :].astype(dt_ref.dtype)


# A kernel's ``name=`` becomes its instruction's name in the compiled
# program (and so in a device trace) only if no transformation wraps it:
# jvp / transpose rewrite the FIRST scope inside them
# (``transpose(jvp(name))``).  So every call site below sits inside one
# more ``jax.named_scope``, which takes the wrapping instead.
@jax.named_scope("flash_backward")
def _flash_dtable(
    qh, doh, oh, lse_b, kh, vh, table, *,
    b, hq, hkv, causal, scale, block_q, block_k, interpret, bucket_cfg,
):
    """The dtable pallas call (see ``_bwd_dtable_kernel``)."""
    _, sq, d = qh.shape
    skv = kh.shape[1]
    n_rep = hq // hkv
    block_q = _shrink_block(block_q, sq)
    block_k = _shrink_block(block_k, skv)
    n_q, n_k = sq // block_q, skv // block_k
    scale_ = scale if scale is not None else 1.0 / math.sqrt(d)
    buckets = bucket_cfg[0]

    q_spec = pl.BlockSpec(
        (1, block_q, d), lambda h, qi, kk, bb: (bb * hq + h, qi, 0)
    )
    res_spec = pl.BlockSpec(
        (None, block_q, _RES_LANES),
        lambda h, qi, kk, bb: (bb * hq + h, qi, 0),
    )
    kv_spec = pl.BlockSpec(
        (1, block_k, d),
        lambda h, qi, kk, bb: (bb * hkv + h // n_rep, kk, 0),
    )
    table_spec = pl.BlockSpec(
        (1, buckets), lambda h, qi, kk, bb: (h, 0)
    )
    return pl.pallas_call(
        functools.partial(
            _bwd_dtable_kernel,
            scale=scale_,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            n_q=n_q,
            n_k=n_k,
            n_b=b,
            diag_offset=skv - sq,
            bucket_cfg=bucket_cfg,
        ),
        grid=(hq, n_q, n_k, b),
        in_specs=[q_spec, q_spec, q_spec, res_spec, kv_spec, kv_spec,
                  table_spec],
        out_specs=table_spec,
        out_shape=jax.ShapeDtypeStruct((hq, buckets), table.dtype),
        scratch_shapes=[pltpu.VMEM((1, buckets), jnp.float32)],
        name="tdx_flash_backward_dtable",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary", "arbitrary", "arbitrary"
            ),
        ),
        interpret=interpret,
    )(qh, doh, oh, lse_b, kh, vh, table)


def _flash_backward(
    q, k, v, out, lse, g, *, causal, scale, block_q, block_k, interpret,
    grad_dtype=None, bias=None, bucket_cfg=None, window=None,
):
    """Pallas FlashAttention-2 backward: two kernels — K/V-stationary for
    dK/dV and Q-stationary for dQ — reconstructing probabilities from the
    saved lse, with ``delta = rowsum(dO * O)`` computed in-kernel.  HBM
    traffic is O(S*D) per head like the forward; the chunked-recompute
    fallback (``_flash_bwd_chunked``) re-ran the whole fused-XLA attention
    per chunk and measured ~2.8x slower per layer on the llama_1b bench
    step (43 ms/step of 210 at seq 2048 — trace, round 3).

    With ``bias`` (the T5 relative-position path) the same two kernels
    stream the bias blocks into the logits recompute, and a third kernel
    (``_bwd_dbias_kernel``) emits dbias with the batch reduction done
    in-VMEM (batch innermost, output-block revisiting) — the whole biased
    backward stays on the kernel path instead of the 2.8x chunked one.

    ``lse`` may come from a LARGER softmax than this K/V block (ring
    attention seeds the global row LSE): probabilities then come out
    partial-but-exact, making the outputs this block's exact gradient
    contributions.  ``grad_dtype`` overrides the output dtypes (the ring
    accumulates block contributions across hops in f32)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    n_rep = hq // hkv
    dkv_dtype = grad_dtype or k.dtype
    dq_dtype = grad_dtype or q.dtype

    qh, doh, oh, lse_b = _prepare_flash_bwd(q, g, out, lse)
    kh = jnp.transpose(k, (0, 2, 1, 3)).reshape(b * hkv, skv, d)
    vh = jnp.transpose(v, (0, 2, 1, 3)).reshape(b * hkv, skv, d)

    dq, dk_part, dv_part = _flash_backward_core(
        qh, doh, oh, lse_b, kh, vh,
        b=b, hq=hq, hkv=hkv,
        causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        dq_dtype=dq_dtype,
        part_dtype=jnp.float32 if n_rep > 1 else dkv_dtype,
        bias=bias, bucket_cfg=bucket_cfg, window=window,
    )

    dq = jnp.transpose(dq.reshape(b, hq, sq, d), (0, 2, 1, 3))
    # heads are grouped g-major (h = g * n_rep + r), so GQA partials fold
    # with one reshape-sum
    dk = jnp.transpose(
        dk_part.reshape(b, hkv, n_rep, skv, d).sum(axis=2).astype(dkv_dtype),
        (0, 2, 1, 3),
    )
    dv = jnp.transpose(
        dv_part.reshape(b, hkv, n_rep, skv, d).sum(axis=2).astype(dkv_dtype),
        (0, 2, 1, 3),
    )
    if bias is None:
        return dq, dk, dv
    if bucket_cfg is not None:
        dtable = _flash_dtable(
            qh, doh, oh, lse_b, kh, vh, bias,
            b=b, hq=hq, hkv=hkv,
            causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
            bucket_cfg=bucket_cfg,
        )
        return dq, dk, dv, dtable
    dbias = _flash_dbias(
        qh, doh, oh, lse_b, kh, vh, bias,
        b=b, hq=hq, hkv=hkv,
        causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return dq, dk, dv, dbias


def _prepare_flash_bwd(q, g, out, lse):
    """Loop-invariant backward operands, head-major: callers that invoke
    the core repeatedly against rotating K/V blocks (ring attention) hoist
    this out of their loop.  Only lse needs the 128-lane broadcast
    layout (the forward's proven residual layout; slimmer layouts are
    unproven here — see _bwd_dkv_kernel); delta is computed in-kernel
    from the O blocks."""
    b, sq, hq, d = q.shape
    qh = jnp.transpose(q, (0, 2, 1, 3)).reshape(b * hq, sq, d)
    doh = jnp.transpose(g, (0, 2, 1, 3)).reshape(b * hq, sq, d)
    oh = jnp.transpose(out, (0, 2, 1, 3)).reshape(b * hq, sq, d)
    lse_b = jnp.broadcast_to(
        lse.reshape(b * hq, sq)[:, :, None], (b * hq, sq, _RES_LANES)
    )
    return qh, doh, oh, lse_b


@jax.named_scope("flash_backward")
def _flash_backward_core(
    qh, doh, oh, lse_b, kh, vh, *,
    b, hq, hkv, causal, scale, block_q, block_k, interpret,
    dq_dtype, part_dtype, bias=None, bucket_cfg=None, window=None,
):
    """The two backward pallas calls over head-major operands (see
    ``_flash_backward``).  Returns head-major ``(dq, dk_part, dv_part)``
    with dK/dV as per-QUERY-head partials (callers fold GQA groups)."""
    _, sq, d = qh.shape
    skv = kh.shape[1]
    n_rep = hq // hkv
    block_q = _shrink_block(block_q, sq)
    block_k = _shrink_block(block_k, skv)
    n_q, n_k = sq // block_q, skv // block_k
    scale_ = scale if scale is not None else 1.0 / math.sqrt(d)
    diag_offset = skv - sq
    has_bias = bias is not None

    def kv_index(c, kk, qi=None):
        return (c // hq) * hkv + (c % hq) // n_rep, kk, 0

    # dK/dV: K/V-stationary, Q innermost
    q_spec = pl.BlockSpec((1, block_q, d), lambda c, kk, qi: (c, qi, 0))
    res_spec = pl.BlockSpec(
        (None, block_q, _RES_LANES), lambda c, kk, qi: (c, qi, 0)
    )
    dkv_in_specs = [
        q_spec,
        q_spec,
        q_spec,
        res_spec,
        pl.BlockSpec((1, block_k, d), lambda c, kk, qi: kv_index(c, kk)),
        pl.BlockSpec((1, block_k, d), lambda c, kk, qi: kv_index(c, kk)),
    ]
    dkv_operands = [qh, doh, oh, lse_b, kh, vh]
    if has_bias:
        if bucket_cfg is not None:
            dkv_in_specs.append(
                pl.BlockSpec(
                    (1, bias.shape[1]), lambda c, kk, qi: (c % hq, 0)
                )
            )
        else:
            dkv_in_specs.append(
                pl.BlockSpec(
                    (1, block_q, block_k), lambda c, kk, qi: (c % hq, qi, kk)
                )
            )
        dkv_operands.append(bias)
    dkv_out_spec = pl.BlockSpec((1, block_k, d), lambda c, kk, qi: (c, kk, 0))
    dk_part, dv_part = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel,
            scale=scale_,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            n_q=n_q,
            diag_offset=diag_offset,
            has_bias=has_bias,
            bucket_cfg=bucket_cfg,
            window=window,
        ),
        grid=(b * hq, n_k, n_q),
        in_specs=dkv_in_specs,
        out_specs=[dkv_out_spec, dkv_out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b * hq, skv, d), part_dtype),
            jax.ShapeDtypeStruct((b * hq, skv, d), part_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        name="tdx_flash_backward_dkv",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*dkv_operands)

    # dQ: Q-stationary, K/V innermost (the forward's schedule)
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda c, qi, kk: (c, qi, 0))
    res_spec2 = pl.BlockSpec(
        (None, block_q, _RES_LANES), lambda c, qi, kk: (c, qi, 0)
    )
    dq_in_specs = [
        q_spec2,
        q_spec2,
        q_spec2,
        res_spec2,
        pl.BlockSpec((1, block_k, d), lambda c, qi, kk: kv_index(c, kk)),
        pl.BlockSpec((1, block_k, d), lambda c, qi, kk: kv_index(c, kk)),
    ]
    dq_operands = [qh, doh, oh, lse_b, kh, vh]
    if has_bias:
        if bucket_cfg is not None:
            dq_in_specs.append(
                pl.BlockSpec(
                    (1, bias.shape[1]), lambda c, qi, kk: (c % hq, 0)
                )
            )
        else:
            dq_in_specs.append(
                pl.BlockSpec(
                    (1, block_q, block_k), lambda c, qi, kk: (c % hq, qi, kk)
                )
            )
        dq_operands.append(bias)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel,
            scale=scale_,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            n_k=n_k,
            diag_offset=diag_offset,
            has_bias=has_bias,
            bucket_cfg=bucket_cfg,
            window=window,
        ),
        grid=(b * hq, n_q, n_k),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec(
            (1, block_q, d), lambda c, qi, kk: (c, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), dq_dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="tdx_flash_backward_dq",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*dq_operands)
    return dq, dk_part, dv_part


@jax.named_scope("flash_backward")
def _flash_dbias(
    qh, doh, oh, lse_b, kh, vh, bias, *,
    b, hq, hkv, causal, scale, block_q, block_k, interpret,
):
    """The dbias pallas call (see ``_bwd_dbias_kernel``): grid
    (hq, n_q, n_k, B) with batch innermost so each (head, q, k) output
    tile is revisited across consecutive batch steps and the batch
    reduction happens in VMEM."""
    _, sq, d = qh.shape
    skv = kh.shape[1]
    n_rep = hq // hkv
    block_q = _shrink_block(block_q, sq)
    block_k = _shrink_block(block_k, skv)
    n_q, n_k = sq // block_q, skv // block_k
    scale_ = scale if scale is not None else 1.0 / math.sqrt(d)
    diag_offset = skv - sq

    q_spec = pl.BlockSpec(
        (1, block_q, d), lambda h, qi, kk, bb: (bb * hq + h, qi, 0)
    )
    res_spec = pl.BlockSpec(
        (None, block_q, _RES_LANES), lambda h, qi, kk, bb: (bb * hq + h, qi, 0)
    )
    kv_spec = pl.BlockSpec(
        (1, block_k, d),
        lambda h, qi, kk, bb: (bb * hkv + h // n_rep, kk, 0),
    )
    bias_spec = pl.BlockSpec(
        (1, block_q, block_k), lambda h, qi, kk, bb: (h, qi, kk)
    )
    return pl.pallas_call(
        functools.partial(
            _bwd_dbias_kernel,
            scale=scale_,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            n_b=b,
            diag_offset=diag_offset,
        ),
        grid=(hq, n_q, n_k, b),
        in_specs=[q_spec, q_spec, q_spec, res_spec, kv_spec, kv_spec,
                  bias_spec],
        out_specs=bias_spec,
        out_shape=jax.ShapeDtypeStruct((hq, sq, skv), bias.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, block_k), jnp.float32)],
        name="tdx_flash_backward_dbias",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            ),
        ),
        interpret=interpret,
    )(qh, doh, oh, lse_b, kh, vh, bias)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10)
)
def _flash_attention_vjp(
    q, k, v, bias, causal, scale, block_q, block_k, interpret, bucket_cfg,
    window,
):
    return _flash_forward(
        q,
        k,
        v,
        bias=bias,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        bucket_cfg=bucket_cfg,
        window=window,
    )


def _flash_fwd_rule(
    q, k, v, bias, causal, scale, block_q, block_k, interpret, bucket_cfg,
    window,
):
    if v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            f"flash attention's backward takes one head width; got qk "
            f"{q.shape[-1]} and v {v.shape[-1]} (forward only)"
        )
    # pallas backward path (biased or not): save the output + per-row lse
    # instead of recomputing the softmax state chunk by chunk — the saved
    # lse includes the bias, so the backward's p = exp(logits + bias - lse)
    # reconstruction is exact
    out, lse = _flash_forward(
        q,
        k,
        v,
        bias=bias,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        return_lse=True,
        bucket_cfg=bucket_cfg,
        window=window,
    )
    return _named_residuals(q, k, v, bias, out, lse)


def _attention_chunk(qc, k, v, bias_rows, row_offset, causal, scale):
    """Reference attention for a Q chunk whose first global row is
    ``row_offset`` (traced), against the full K/V.  f32 softmax, same math
    as ``multihead_attention``.  ``bias_rows``: optional (H, cq, Skv)
    additive logit bias slice."""
    b, cq, hq, d = qc.shape
    _, skv, hkv, _ = k.shape
    if hq != hkv:
        n_rep = hq // hkv
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qc, k).astype(jnp.float32) * s
    if bias_rows is not None:
        logits = logits + bias_rows[None].astype(jnp.float32)
    if causal:
        rows = row_offset + jnp.arange(cq)[:, None]
        cols = jnp.arange(skv)[None, :]
        logits = jnp.where(cols <= rows, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(qc.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# Benchmarking knob: force the biased backward onto the retired
# chunked-recompute path so the kernel-vs-chunked delta stays measurable
# (scripts/bench_flash_attention.py --bias).  Never set in production.
_FORCE_CHUNKED_BWD = False


def _flash_bwd_rule(
    causal, scale, block_q, block_k, interpret, bucket_cfg, window, res, g
):
    q, k, v, bias, out, lse = res
    if _FORCE_CHUNKED_BWD and bias is not None and bucket_cfg is None:
        return _flash_bwd_chunked(q, k, v, bias, g, causal, scale, block_q)
    # pallas FlashAttention-2 backward (see _flash_backward); with bias a
    # third kernel emits dbias (or dtable for the in-kernel bucket mode).
    # _flash_bwd_chunked remains only as the reference implementation the
    # parity tests compare against.
    grads = _flash_backward(
        q, k, v, out, lse, g,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        bias=bias,
        bucket_cfg=bucket_cfg,
        window=window,
    )
    if bias is None:
        dq, dk, dv = grads
        return dq, dk, dv, None
    return grads


def _flash_bwd_chunked(q, k, v, bias, g, causal, scale, block_q):
    # Backward by CHUNKED recomputation: each Q chunk's attention is
    # recomputed with XLA and differentiated via jax.vjp, accumulating
    # dK/dV across chunks under lax.scan.  Peak memory is O(chunk * Skv) —
    # the flash working-set profile — instead of the O(Sq * Skv) a
    # whole-matrix recompute would allocate.  Since round 4 this is NOT on
    # the production path (the pallas kernels handle bias + dbias); it
    # stays as the independent reference implementation the parity tests
    # diff the kernels against.
    b, sq, hq, d = q.shape
    _, skv, _, _ = k.shape
    chunk = _shrink_block(block_q, sq)
    n_chunks = sq // chunk
    diag_offset = skv - sq

    def body(carry, idx):
        dk_acc, dv_acc = carry
        qs = jax.lax.dynamic_slice_in_dim(q, idx * chunk, chunk, axis=1)
        gs = jax.lax.dynamic_slice_in_dim(g, idx * chunk, chunk, axis=1)
        row_offset = idx * chunk + diag_offset
        bs = jax.lax.dynamic_slice_in_dim(bias, idx * chunk, chunk, axis=1)

        def chunk_fn(q_, k_, v_, b_):
            return _attention_chunk(
                q_, k_, v_, b_, row_offset, causal, scale
            )

        _, vjp = jax.vjp(chunk_fn, qs, k, v, bs)
        dq_c, dk_c, dv_c, db_c = vjp(gs)
        return (dk_acc + dk_c, dv_acc + dv_c), (dq_c, db_c)

    (dk, dv), (dq_chunks, db_chunks) = jax.lax.scan(
        body,
        (jnp.zeros_like(k), jnp.zeros_like(v)),
        jnp.arange(n_chunks),
    )
    # (n_chunks, B, chunk, H, D) -> (B, Sq, H, D)
    dq = jnp.moveaxis(dq_chunks, 0, 1).reshape(b, sq, hq, d)
    # (n_chunks, H, chunk, Skv) -> (H, Sq, Skv)
    dbias = jnp.moveaxis(db_chunks, 0, 1).reshape(hq, sq, skv).astype(bias.dtype)
    return dq, dk, dv, dbias


_flash_attention_vjp.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def resolve_use_flash(setting: Optional[bool]) -> bool:
    """Shared model-config policy: ``None`` means auto — flash on TPU
    (measured 2-5x and the only runnable path at 8k+,
    scripts/bench_flash_attention.py), the jnp path elsewhere (the CPU
    fallback is interpret-mode pallas: exact but slow)."""
    if setting is not None:
        return bool(setting)
    return jax.devices()[0].platform == "tpu"


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    bias: Optional[jax.Array] = None,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    rel_bias_table: Optional[jax.Array] = None,
    rel_bias_buckets: int = 32,
    rel_bias_max_dist: int = 128,
    rel_bias_bidirectional: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Differentiable entry point: flash kernel forward; the backward is
    the pallas FlashAttention-2 kernel pair (``_flash_backward``) —
    residuals are the output and per-row lse, NOT a recompute.  With
    ``bias`` a third kernel emits dbias (batch reduction in-VMEM), so the
    biased path stays on kernels too (round 3 it fell back to the 2.8x
    chunked recompute).

    ``bias``: optional additive logit bias of shape (Hq, Sq, Skv), shared
    across the batch — T5's relative-position bias.  Streamed blockwise
    into the kernel; differentiable (the backward emits dbias).

    ``rel_bias_table``: optional (Hq, buckets) bucket table — the
    IN-KERNEL bias mode: each tile computes its bias from bucket ids and
    the per-head table in VMEM, so no (Hq, Sq, Skv) bias ever
    materializes (T5 long context keeps flash's O(S) memory).
    Differentiable: the backward emits dtable via a fourth kernel.
    Requires Sq == Skv; mutually exclusive with ``bias``.

    ``block_q`` / ``block_k``: upper bounds of a tile, each halved until
    it divides its sequence length; ``None`` takes the module's
    (``_BLOCKS``; ``_BLOCKS_SMALL`` for float32 rows and in either bias
    mode, whose tiles hold more bytes).

    ``window``: sliding-window attention (Mistral/Mixtral) — query ``i``
    attends keys ``(i - window, i]``.  Requires ``causal=True``; blocks
    outside the band are pruned at the grid level, so compute scales
    with ``S * window`` instead of ``S^2``.  Mutually exclusive with
    ``bias``/``rel_bias_table`` (no windowed-bias model family exists to
    pin the combined semantics against).
    """
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if bias is not None or rel_bias_table is not None:
            raise ValueError(
                "window is mutually exclusive with bias/rel_bias_table"
            )
    if rel_bias_table is not None:
        if bias is not None:
            raise ValueError("pass bias OR rel_bias_table, not both")
        bias = rel_bias_table
        bucket_cfg = (
            int(rel_bias_buckets),
            int(rel_bias_max_dist),
            bool(rel_bias_bidirectional),
        )
    else:
        bucket_cfg = None
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    wide = max(jnp.dtype(x.dtype).itemsize for x in (q, k, v)) > 2
    bound_q, bound_k = _BLOCKS_SMALL if wide or bias is not None else _BLOCKS
    block_q = bound_q if block_q is None else block_q
    block_k = bound_k if block_k is None else block_k
    return _flash_attention_vjp(
        q, k, v, bias, causal, scale, block_q, block_k, interpret,
        bucket_cfg, window,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "block_q", "block_k", "interpret",
        "return_residuals", "return_lse", "bucket_cfg", "window",
    ),
)
def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    bias: Optional[jax.Array] = None,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = _BLOCKS_SMALL[0],
    block_k: int = _BLOCKS_SMALL[1],
    interpret: Optional[bool] = None,
    return_residuals: bool = False,
    return_lse: bool = False,
    bucket_cfg: Optional[tuple] = None,
    window: Optional[int] = None,
):
    """(B, Sq, Hq, D) x (B, Skv, Hkv, D)^2 -> (B, Sq, Hq, D).

    The values may be narrower or wider than the keys (``v``:
    (B, Skv, Hkv, Dv), the output then (B, Sq, Hq, Dv)): multi-head
    latent attention scores on 192 lanes and sums 128-wide values.
    Forward only; the backward kernels assume one width.

    With ``bucket_cfg = (buckets, max_dist, bidirectional)`` the ``bias``
    operand is the per-head bucket TABLE of shape (Hq, buckets) instead
    of a materialized (Hq, Sq, Skv) bias: each kernel tile computes its
    bias from bucket ids in-VMEM (``_bucket_bias_tile``), so T5-style
    relative-position attention keeps flash's O(S) memory.  Requires
    Sq == Skv.

    ``block_q``/``block_k`` are upper bounds: each is halved until it
    divides its sequence length, so any length works.  ``interpret``
    defaults to True off-TPU so the same code runs (slowly but exactly) on
    CPU platforms.

    ``return_residuals=True`` additionally returns the per-row
    online-softmax state ``(m, l)`` of shape (B, Hq, Sq) — running max and
    sum of exp(logits - m) — which ring attention's cross-block combine
    consumes (ops/attention.py ``ring_flash_attention``).  In that mode
    the primary output is the RAW f32 accumulator (sum of
    exp(logits - m) @ V, not divided by ``l``, no dtype rounding): the
    consumer's combine re-scales blocks in pure f32 and normalizes once
    at the end.

    ``return_lse=True`` (exclusive with ``return_residuals``) returns the
    NORMALIZED output plus per-row ``lse = m + log(l)`` of shape
    (B, Hq, Sq) — the residual the pallas backward consumes.
    """
    if return_residuals and return_lse:
        raise ValueError("return_residuals and return_lse are exclusive")
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    if hq % hkv != 0:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    if causal and sq > skv:
        # every extra trailing query row would have an empty key set — the
        # reference returns NaN there; fail loudly instead of diverging
        raise ValueError(
            f"causal attention requires Sq ({sq}) <= Skv ({skv})"
        )
    n_rep = hq // hkv
    block_q = _shrink_block(block_q, sq)
    block_k = _shrink_block(block_k, skv)
    scale_ = scale if scale is not None else 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    n_k = skv // block_k

    qh = jnp.transpose(q, (0, 2, 1, 3)).reshape(b * hq, sq, d)
    kh = jnp.transpose(k, (0, 2, 1, 3)).reshape(b * hkv, skv, d)
    vh = jnp.transpose(v, (0, 2, 1, 3)).reshape(b * hkv, skv, dv)

    def kv_index(c, i, kk):
        # combined q index c = batch * hq + h  ->  batch * hkv + h // n_rep
        return (c // hq) * hkv + (c % hq) // n_rep, kk, 0

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda c, i, kk: (c, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, dv), kv_index),
    ]
    operands = [qh, kh, vh]
    if bias is not None:
        if bucket_cfg is not None:
            if sq != skv:
                raise ValueError(
                    "in-kernel bucket bias requires Sq == Skv "
                    f"(got {sq} vs {skv})"
                )
            if bias.shape != (hq, bucket_cfg[0]):
                raise ValueError(
                    f"bucket-bias table shape {bias.shape} != "
                    f"(Hq, buckets) = {(hq, bucket_cfg[0])}"
                )
            # the whole per-head table rides into VMEM: (1, buckets)
            # block, head selected by the index map
            in_specs.append(
                pl.BlockSpec(
                    (1, bias.shape[1]), lambda c, i, kk: (c % hq, 0)
                )
            )
        else:
            if bias.shape != (hq, sq, skv):
                raise ValueError(
                    f"bias shape {bias.shape} != (Hq, Sq, Skv) = "
                    f"{(hq, sq, skv)}"
                )
            # bias is shared across the batch: program c maps to head c % hq
            in_specs.append(
                pl.BlockSpec(
                    (1, block_q, block_k), lambda c, i, kk: (c % hq, i, kk)
                )
            )
        operands.append(bias)

    out_specs = [pl.BlockSpec((1, block_q, dv), lambda c, i, kk: (c, i, 0))]
    out_shape = [
        jax.ShapeDtypeStruct(
            (b * hq, sq, dv),
            jnp.float32 if return_residuals else q.dtype,
        )
    ]
    multi_out = return_residuals or return_lse
    if multi_out:
        res_spec = pl.BlockSpec(
            (None, block_q, _RES_LANES), lambda c, i, kk: (c, i, 0)
        )
        res_shape = jax.ShapeDtypeStruct(
            (b * hq, sq, _RES_LANES), jnp.float32
        )
        if return_residuals:
            out_specs += [res_spec, res_spec]
            out_shape += [res_shape, res_shape]
        else:
            out_specs += [res_spec]
            out_shape += [res_shape]

    outs = pl.pallas_call(
        functools.partial(
            _kernel,
            scale=scale_,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            n_k=n_k,
            diag_offset=skv - sq,
            has_bias=bias is not None,
            emit_residuals=return_residuals,
            emit_lse=return_lse,
            bucket_cfg=bucket_cfg,
            window=window,
        ),
        grid=(b * hq, sq // block_q, n_k),
        in_specs=in_specs,
        out_specs=out_specs if multi_out else out_specs[0],
        out_shape=out_shape if multi_out else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        name="tdx_flash_forward",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    if not multi_out:
        return jnp.transpose(outs.reshape(b, hq, sq, dv), (0, 2, 1, 3))
    if return_lse:
        out, lse = outs
        out = jnp.transpose(out.reshape(b, hq, sq, dv), (0, 2, 1, 3))
        return out, lse[..., 0].reshape(b, hq, sq)
    out, m, l = outs
    out = jnp.transpose(out.reshape(b, hq, sq, dv), (0, 2, 1, 3))
    return (
        out,
        m[..., 0].reshape(b, hq, sq),
        l[..., 0].reshape(b, hq, sq),
    )


# The forward rule names its residuals: a block checkpoint that keeps its
# matmul outputs keeps these too (nn/remat.py), so the backward does not
# run the forward kernel again to get them.
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _named_residuals(q, k, v, bias, out, lse):
    """``_flash_fwd_rule``'s result: the output and the residuals, with
    ``out`` and ``lse`` under ``RESIDUAL_NAMES``."""
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return out, (q, k, v, bias, out, lse)
