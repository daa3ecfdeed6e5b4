"""Fused LM-head + cross-entropy pallas kernels: the vocab-bandwidth lever.

The round-3 on-chip profile (BASELINE.md) put ~15 ms/step of the llama_1b
bench in "vocab-table fusions" running at ~300 GB/s: the LM head emits a
(tokens, vocab) logits matrix (262 MB bf16 at 2x2048x32000), the loss
casts it to f32 (doubling it), log-softmax re-reads it, and the backward
materializes dlogits at the same size before the dX/dW matmuls re-read it.
None of those bytes need to exist: cross-entropy only needs per-token
``(lse, z_label)`` statistics forward and the rank-limited products
``dX = dP @ W`` / ``dW = dP^T @ X`` backward, where every dP tile is a
cheap recompute from the saved lse.

Three kernels, all streaming W in (block_v, D) tiles so the logits matrix
only ever exists one VMEM tile at a time:

- ``_fwd_kernel``  — token-stationary, vocab innermost: online max/sumexp
  (the softmax half of the flash-attention schedule) plus the label
  logit picked up by an in-tile column match; emits per-token loss + lse.
- ``_dx_kernel``   — token-stationary: recomputes each logits tile from
  (X, W, lse), forms ``dP = softmax - onehot`` in registers, accumulates
  ``dX += dP @ W_tile`` in VMEM.
- ``_dw_kernel``   — vocab-stationary, tokens innermost: same recompute,
  accumulates ``dW += dP^T @ X_tile`` in VMEM.

HBM traffic drops from ~5 logits-sized passes to three streams of W
(~400 MB at the bench shape vs ~1.8 GB) — the arithmetic is the same
matmul FLOPs the unfused path already pays.

Opt-in: Mosaic accepts the kernels at the bench shapes
(tests/test_chip_compile.py), but no chip run has compared them with the
unfused loss yet (ROADMAP A3/C9).  There is no config knob:
callers ask the model for hidden states — ``model.forward(tokens,
return_hidden=True)`` (Llama and GPT-2 both take it) — and call
``fused_linear_cross_entropy(hidden, head_weight, labels)`` directly in
their loss, where ``head_weight`` is ``lm_head.weight`` (GPT-2: the tied
``tok_emb.weight``).  The bench workload flips to that path under
``TDX_BENCH_FUSED_CE=1`` (utils/benchmarks.py), and the ``fusedce``
phase of ``scripts/verify_kernels_onchip.py`` captures the
compiled-vs-reference evidence.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _RES_LANES, _shrink_block

__all__ = ["fused_linear_cross_entropy"]


def _logits_tile(x_ref, w_ref, vi, *, block_v: int, v_true: int):
    """(block_t, block_v) f32 logits tile, with columns beyond the TRUE
    vocab (zero-padded W rows — see ``_blocks``) masked to -inf so they
    vanish from the softmax and from every gradient."""
    x = x_ref[...].astype(jnp.float32)  # (block_t, D)
    w = w_ref[...].astype(jnp.float32)  # (block_v, D)
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (block_t, block_v)
    cols = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1
    )
    if v_true % block_v != 0:  # only the padded case pays the select
        logits = jnp.where(cols < v_true, logits, -1e30)
    return logits, cols, x, w


def _fwd_kernel(
    x_ref, w_ref, lab_ref, loss_ref, lse_ref, m_ref, l_ref, zy_ref,
    *, block_t: int, block_v: int, n_v: int, v_true: int,
):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)
        zy_ref[:] = jnp.zeros_like(zy_ref)

    logits, cols, _, _ = _logits_tile(
        x_ref, w_ref, vi, block_v=block_v, v_true=v_true
    )

    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    l_ref[:] = l_ref[:] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logits - m_new), axis=-1, keepdims=True
    )
    m_ref[:] = m_new

    # label logit: the (single) column of this tile matching the token's
    # label contributes; every token's label lands in exactly one tile
    labels = lab_ref[...][:, :1]  # (block_t, 1) int32
    zy_ref[:] = zy_ref[:] + jnp.sum(
        jnp.where(cols == labels, logits, 0.0), axis=-1, keepdims=True
    )

    @pl.when(vi == n_v - 1)
    def _emit():
        lse = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))
        loss_ref[...] = jnp.broadcast_to(lse - zy_ref[:], loss_ref.shape)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _row_mask(dp, ti, *, block_t: int, n_true: int):
    """Zero dp rows beyond the TRUE token count (zero-padded X rows —
    see ``_blocks``); their softmax rows are garbage and must not leak
    into dX/dW."""
    if n_true % block_t == 0:
        return dp
    rows = ti * block_t + jax.lax.broadcasted_iota(
        jnp.int32, dp.shape, 0
    )
    return jnp.where(rows < n_true, dp, 0.0)


def _dx_kernel(
    x_ref, w_ref, lab_ref, lse_ref, dx_ref, dx_acc,
    *, block_t: int, block_v: int, n_v: int, inv_n: float, v_true: int,
    n_true: int,
):
    ti = pl.program_id(0)
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        dx_acc[:] = jnp.zeros_like(dx_acc)

    logits, cols, _, w = _logits_tile(
        x_ref, w_ref, vi, block_v=block_v, v_true=v_true
    )
    lse = lse_ref[...][:, :1]
    p = jnp.exp(logits - lse)  # exactly 0 at padded columns
    labels = lab_ref[...][:, :1]
    dp = (p - jnp.where(cols == labels, 1.0, 0.0)) * inv_n
    dp = _row_mask(dp, ti, block_t=block_t, n_true=n_true)
    dx_acc[:] = dx_acc[:] + jax.lax.dot_general(
        dp, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(vi == n_v - 1)
    def _emit():
        dx_ref[...] = dx_acc[:].astype(dx_ref.dtype)


def _dw_kernel(
    x_ref, w_ref, lab_ref, lse_ref, dw_ref, dw_acc,
    *, block_t: int, block_v: int, n_t: int, inv_n: float, v_true: int,
    n_true: int,
):
    vi = pl.program_id(0)
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        dw_acc[:] = jnp.zeros_like(dw_acc)

    logits, cols, x, _ = _logits_tile(
        x_ref, w_ref, vi, block_v=block_v, v_true=v_true
    )
    lse = lse_ref[...][:, :1]
    p = jnp.exp(logits - lse)  # exactly 0 at padded columns
    labels = lab_ref[...][:, :1]
    dp = (p - jnp.where(cols == labels, 1.0, 0.0)) * inv_n
    dp = _row_mask(dp, ti, block_t=block_t, n_true=n_true)
    # dW_tile += dP^T @ X : (block_v, D)
    dw_acc[:] = dw_acc[:] + jax.lax.dot_general(
        dp, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ti == n_t - 1)
    def _emit():
        dw_ref[...] = dw_acc[:].astype(dw_ref.dtype)


def _blocks(n: int, v: int, block_t: int, block_v: int):
    """Token/vocab tiling.  Dimensions with no good divisor are PADDED
    up to a block multiple instead of shrinking the block (GPT-2's vocab
    50257 = 7*43*167 would shrink block_v to 1 — a 50k-step grid; a
    prime token count does the same to block_t): padded vocab columns
    are masked to -inf in-kernel (``_logits_tile``) and padded token
    rows are zeroed out of dX/dW (``_row_mask``), so neither reaches
    the softmax, the loss mean, or any gradient; the wrappers slice
    dW/dX back to the true extents.
    Returns (bt, bv, n_t, n_v, v_pad, n_pad)."""
    bt = _shrink_block(block_t, n)
    if n < 8:
        # compiled Mosaic needs >= 8 sublanes per block: a tiny token
        # count (n < 8 divides itself, so no shrink/pad path fired) must
        # still pad up to one 8-row block
        bt, n_pad = 8, 8
    elif bt < 8:  # same hazard on the token dim (odd batch*seq)
        bt = block_t
        n_pad = -(-n // bt) * bt
    else:
        n_pad = n
    bv = _shrink_block(block_v, v)
    if bv < 128 and v > 128:
        bv = block_v  # honor the caller's tile bound; pad V up to it
        v_pad = -(-v // bv) * bv
    else:
        v_pad = v
    return bt, bv, n_pad // bt, v_pad // bv, v_pad, n_pad


def _broadcast_lanes(a):
    return jnp.broadcast_to(a[:, None], (a.shape[0], _RES_LANES))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_ce(x, w, labels, block_t, block_v, interpret):
    loss, _ = _fused_ce_fwd_impl(x, w, labels, block_t, block_v, interpret)
    return loss


# one more scope around each kernel's call site, so that jvp / transpose
# wrap IT and the kernel's ``name=`` reaches the instruction unchanged
# (ops/flash_attention.py has the long form of this note)
@jax.named_scope("fused_ce")
def _fused_ce_fwd_impl(x, w, labels, block_t, block_v, interpret):
    n, d = x.shape
    v = w.shape[0]
    bt, bv, n_t, n_v, v_pad, n_pad = _blocks(n, v, block_t, block_v)
    if v_pad != v:
        w = jnp.pad(w, ((0, v_pad - v), (0, 0)))
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        labels = jnp.pad(labels, (0, n_pad - n))
    lab_b = _broadcast_lanes(labels.astype(jnp.int32))
    res_spec = pl.BlockSpec((bt, _RES_LANES), lambda ti, vi: (ti, 0))
    loss_rows, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_t=bt, block_v=bv, n_v=n_v, v_true=v
        ),
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((bt, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bv, d), lambda ti, vi: (vi, 0)),
            res_spec,
        ],
        out_specs=[res_spec, res_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, _RES_LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, _RES_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
        ],
        name="tdx_fused_ce_forward",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w, lab_b)
    return jnp.mean(loss_rows[:n, 0]), lse


def _fused_ce_fwd(x, w, labels, block_t, block_v, interpret):
    loss, lse = _fused_ce_fwd_impl(x, w, labels, block_t, block_v, interpret)
    return loss, (x, w, labels, lse)


@jax.named_scope("fused_ce")
def _fused_ce_bwd(block_t, block_v, interpret, res, g):
    x, w, labels, lse = res
    n, d = x.shape
    v = w.shape[0]
    bt, bv, n_t, n_v, v_pad, n_pad = _blocks(n, v, block_t, block_v)
    if v_pad != v:
        w = jnp.pad(w, ((0, v_pad - v), (0, 0)))
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        labels = jnp.pad(labels, (0, n_pad - n))
    inv_n = 1.0 / n
    lab_b = _broadcast_lanes(labels.astype(jnp.int32))
    res_spec_t = pl.BlockSpec((bt, _RES_LANES), lambda ti, vi: (ti, 0))

    dx = pl.pallas_call(
        functools.partial(
            _dx_kernel, block_t=bt, block_v=bv, n_v=n_v, inv_n=inv_n,
            v_true=v, n_true=n,
        ),
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((bt, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bv, d), lambda ti, vi: (vi, 0)),
            res_spec_t,
            res_spec_t,
        ],
        out_specs=pl.BlockSpec((bt, d), lambda ti, vi: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        name="tdx_fused_ce_backward_dx",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w, lab_b, lse)

    res_spec_v = pl.BlockSpec((bt, _RES_LANES), lambda vi, ti: (ti, 0))
    dw = pl.pallas_call(
        functools.partial(
            _dw_kernel, block_t=bt, block_v=bv, n_t=n_t, inv_n=inv_n,
            v_true=v, n_true=n,
        ),
        grid=(n_v, n_t),
        in_specs=[
            pl.BlockSpec((bt, d), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((bv, d), lambda vi, ti: (vi, 0)),
            res_spec_v,
            res_spec_v,
        ],
        out_specs=pl.BlockSpec((bv, d), lambda vi, ti: (vi, 0)),
        out_shape=jax.ShapeDtypeStruct((v_pad, d), w.dtype),
        scratch_shapes=[pltpu.VMEM((bv, d), jnp.float32)],
        name="tdx_fused_ce_backward_dw",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w, lab_b, lse)

    if v_pad != v:
        dw = dw[:v]  # padded rows carry exact zeros; drop them
    if n_pad != n:
        dx = dx[:n]
    gf = g.astype(jnp.float32)
    return (
        (dx.astype(jnp.float32) * gf).astype(x.dtype),
        (dw.astype(jnp.float32) * gf).astype(w.dtype),
        None,
    )


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_linear_cross_entropy(
    x: jax.Array,
    w: jax.Array,
    labels: jax.Array,
    *,
    block_t: int = 256,
    block_v: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Mean token cross-entropy of the LM head ``logits = x @ w.T``
    WITHOUT materializing the logits (module docstring).

    Args:
      x: (..., N, D) hidden states (any leading dims are flattened).
      w: (V, D) LM-head weight (``nn.Linear``'s (out, in) layout).
      labels: integer labels, same leading shape as ``x`` minus D.

    Exactly ``nn.functional.cross_entropy(x @ w.T, labels)`` up to f32
    accumulation order (parity pinned in tests/test_fused_ce.py).
    Differentiable in ``x`` and ``w``.  ``block_t``/``block_v`` are upper
    bounds shrunk to divide the flattened token count / vocab; a
    dimension with no good divisor (GPT-2's 50257-entry vocab, a prime
    token count) is instead PADDED up to a block multiple, with the
    padded columns/rows masked in-kernel and dW/dX sliced back to the
    true extents.
    """
    d = x.shape[-1]
    if w.ndim != 2 or w.shape[1] != d:
        raise ValueError(f"w must be (V, {d}), got {w.shape}")
    xf = x.reshape(-1, d)
    lf = labels.reshape(-1)
    if lf.shape[0] != xf.shape[0]:
        raise ValueError(
            f"labels {labels.shape} do not match tokens {x.shape[:-1]}"
        )
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return _fused_ce(xf, w, lf, int(block_t), int(block_v), bool(interpret))
