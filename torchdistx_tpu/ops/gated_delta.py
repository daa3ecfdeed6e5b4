"""Pallas gated delta rule (Gated DeltaNet): ``tdx_gated_delta_chunk`` for
a prefill, ``tdx_gated_delta_update`` for a decode step.

A Gated-DeltaNet mixer keeps, per value head, a MATRIX ``S`` of ``Dk``
key lanes by ``Dv`` value lanes (``models/qwen3_next.py`` has the whole
layer).  A token decays it, reads it, corrects it by a rank-one term and
reads it again::

    S' = exp(g_t) * S_{t-1}                 # g_t <= 0: a scalar a head
    d  = beta_t * (v_t - S'^T k_t)          # (Dv,): what the state gets wrong about v_t
    S_t = S' + k_t d^T
    o_t = S_t^T q_t                         # (Dv,)

``k`` is L2-normalised and ``q`` normalised and scaled by the caller;
each key head serves ``Hv / Hk`` value heads (the kernels pick a value
head's key head in their index maps: nothing is repeated in memory).
The correction READS the state before it writes it, so the recurrence
is neither a diagonal scan (``ops/selective_scan.py``) nor a plain
linear attention.

``tdx_gated_delta_update``: one token for each of ``S`` slots, grid
``(slot blocks, head blocks)``; a head's 64 KB of state is read,
decayed, corrected and written IN PLACE (``input_output_aliases``: the
serve engine's slab is donated to its programs) and ``o`` comes from
the same resident block.  All of it is VPU work over ``(Dk, Dv)``
tiles: ``k`` and ``q`` arrive as columns (key lanes on sublanes, the
block's key heads on lanes), so a head's ``k[:, None] * d[None, :]`` is
two broadcasts and no transpose.

``tdx_gated_delta_chunk``: the chunked (WY) form, grid ``(batch, value
heads, chunks)``, the chunks innermost and sequential with the running
``S`` in VMEM (float32).  Within a chunk of ``C`` rows, with ``G`` the
running sum of ``g`` inside the chunk and ``S0`` the state at its start::

    A[i, j] = beta_i * exp(G_i - G_j) * (k_i . k_j)   for j < i, else 0
    U = (I + A)^-1 (beta * V - (beta * exp(G) * K) S0)           # the corrections d, all rows at once
    O = (exp(G) * Q) S0 + (tril(Q K^T) * exp(G_i - G_j)) U
    S_C = exp(G_C) * S0 + (K * exp(G_C - G))^T U

``A`` is strictly lower triangular, so ``-A`` is nilpotent at ``C`` and
``(I + A)^-1 = (I - A)(I + A^2)(I + A^4)...``: ``log2(C)`` squarings and
as many products, matmuls alone, no substitution loop.  ``true_len``
(scalar-prefetched, one a batch row) is how many leading rows are real:
rows at and past it get ``g = 0`` and ``beta = 0`` (decay 1, no
correction: the wrapper masks them) and chunks wholly past it are
skipped, so a prompt right-padded to a bucket writes the state after
its last REAL token.

Each kernel stands beside ``jax.numpy`` forms: the token-by-token
recurrence (``gated_delta_recurrence_jnp``, the oracle of every test)
and the same chunked arithmetic (``gated_delta_chunk_jnp``, the path off
the chip; ``use_kernel=None`` is the repo's convention: the kernel on a
TPU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import _interpret
from .flash_attention import resolve_use_flash

__all__ = [
    "gated_delta_chunk",
    "gated_delta_chunk_jnp",
    "gated_delta_recurrence_jnp",
    "gated_delta_update",
    "gated_delta_update_jnp",
]

CHUNK_KERNEL_NAME = "tdx_gated_delta_chunk"
UPDATE_KERNEL_NAME = "tdx_gated_delta_update"

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def _per_value_head(x, hv: int):
    """``x`` (..., Hk, D) -> (..., Hv, D): a key head for each of the
    value heads it serves (the jnp forms only)."""
    return jnp.repeat(x, hv // x.shape[-2], axis=-2)


def _chunking(block_t: int, length: int):
    """``(rows a chunk, rows after padding)``: ``block_t`` rows, or a
    short sequence whole (rounded up to the sublanes)."""
    size = min(block_t, -(-length // 8) * 8)
    return size, -(-length // size) * size


def _masked(g, beta, true_len):
    """``g``, ``beta`` (B, L, Hv) with the rows at and past ``true_len``
    (a scalar or (B,)) made to leave the state as it is."""
    bsz, length, _ = g.shape
    lens = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32), (bsz,))
    real = (jnp.arange(length)[None, :] < lens[:, None])[..., None]
    return (jnp.where(real, g.astype(_F32), 0.0),
            jnp.where(real, beta.astype(_F32), 0.0), lens)


# -- the jnp forms: the path off the chip, and the tests' oracle ------------


def gated_delta_update_jnp(state, q, k, v, g, beta):
    """One token a row.  ``state`` (B, Hv, Dk, Dv) float32; ``q``, ``k``
    (B, Hk, Dk); ``v`` (B, Hv, Dv); ``g``, ``beta`` (B, Hv).  Returns
    ``(o (B, Hv, Dv) in v.dtype, state_new)``."""
    hv = state.shape[1]
    qf = _per_value_head(q.astype(_F32), hv)
    kf = _per_value_head(k.astype(_F32), hv)
    s = jnp.exp(g.astype(_F32))[..., None, None] * state
    ks = jnp.einsum("bhk,bhkv->bhv", kf, s, precision=_HIGHEST)
    d = beta.astype(_F32)[..., None] * (v.astype(_F32) - ks)
    s = s + kf[..., :, None] * d[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", qf, s, precision=_HIGHEST)
    return o.astype(v.dtype), s


def gated_delta_recurrence_jnp(q, k, v, g, beta, state0, true_len):
    """``L`` tokens a row, one after another (a ``lax.scan`` of
    :func:`gated_delta_update_jnp`): the oracle.  ``q``, ``k`` (B, L,
    Hk, Dk); ``v`` (B, L, Hv, Dv); ``g``, ``beta`` (B, L, Hv);
    ``state0`` (B, Hv, Dk, Dv).  Returns ``(o (B, L, Hv, Dv), the state
    after true_len rows)``."""
    g, beta, _ = _masked(g, beta, true_len)

    def step(s, row):
        o, s = gated_delta_update_jnp(s, *row)
        return s, o

    rows = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    s, o = lax.scan(step, state0.astype(_F32), rows)
    return jnp.moveaxis(o, 0, 1), s


def _inverse_unit_lower(a, size: int):
    """``(I + a)^-1`` for a strictly lower triangular ``a`` (..., C, C):
    ``-a`` is nilpotent at ``C``, so the inverse is the finite product
    ``(I - a)(I + a^2)(I + a^4)...``."""
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    p = -a
    t = jnp.eye(size, dtype=_F32) + p
    for _ in range(max(size - 1, 1).bit_length() - 1):
        p = mm(p, p)
        t = t + mm(t, p)
    return t


def _chunk_math(q, k, v, gc, g_row, beta, s0, size: int):
    """One chunk of one head, the module docstring's four lines: ``q``,
    ``k`` (C, Dk); ``v`` (C, Dv); ``gc`` (the running sum of ``g``) and
    ``beta`` as columns (C, 1), ``g_row`` the same sum as a row (1, C),
    so that nothing is transposed here; ``s0`` (Dk, Dv).  Shared by the
    jnp form (under ``vmap``) and the kernel's body."""
    mm = functools.partial(jnp.dot, precision=_HIGHEST,
                           preferred_element_type=_F32)
    nt = (((1,), (1,)), ((), ()))  # a @ b^T
    tn = (((0,), (0,)), ((), ()))  # a^T @ b
    dg = functools.partial(lax.dot_general, precision=_HIGHEST,
                           preferred_element_type=_F32)
    i = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    j = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    # exp(G_i - G_j) for j <= i (never above 1), else 0
    decay = jnp.exp(jnp.where(i >= j, gc - g_row, -jnp.inf))
    a = jnp.where(i > j, beta * dg(k, k, nt) * decay, 0.0)
    t = _inverse_unit_lower(a, size)
    e_g = jnp.exp(gc)
    u = mm(t, beta * v - mm(beta * e_g * k, s0))
    o = mm(e_g * q, s0) + mm(dg(q, k, nt) * decay, u)
    g_last = gc[size - 1:size]  # (1, 1)
    # over the lanes first: Mosaic broadcasts along one axis at a time
    last_row = jnp.broadcast_to(g_last, (1, s0.shape[1]))
    s = jnp.exp(last_row) * s0 + dg(k * jnp.exp(g_last - gc), u, tn)
    return o, s


def gated_delta_chunk_jnp(q, k, v, g, beta, state0, true_len, *,
                          block_t: int = 128):
    """The chunked form in plain ``jax.numpy`` (shapes as
    :func:`gated_delta_recurrence_jnp`): a ``lax.scan`` over chunks of
    ``block_t`` rows, every head of every row at once."""
    bsz, length, hv, dv = v.shape
    g, beta, _ = _masked(g, beta, true_len)
    size, padded = _chunking(block_t, length)
    n = padded // size

    def chunks(x):  # (B, L, H, D) -> (n, B, H, C, D), padding rows zero
        x = jnp.pad(x.astype(_F32),
                    ((0, 0), (0, padded - length), (0, 0), (0, 0)))
        x = x.reshape(bsz, n, size, x.shape[2], x.shape[3])
        return jnp.transpose(x, (1, 0, 3, 2, 4))

    qc = chunks(_per_value_head(q, hv))
    kc = chunks(_per_value_head(k, hv))
    vc = chunks(v)
    gcum = jnp.cumsum(chunks(g[..., None]), axis=3)
    bc = chunks(beta[..., None])
    math = jax.vmap(jax.vmap(functools.partial(_chunk_math, size=size)))

    def step(s, row):
        o, s = math(*row, s)
        return s, o

    s, o = lax.scan(
        step, state0.astype(_F32),
        (qc, kc, vc, gcum, jnp.swapaxes(gcum, 3, 4), bc),
    )
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(bsz, padded, hv, dv)
    return o[:, :length].astype(v.dtype), s


# -- the prefill kernel -----------------------------------------------------


def _chunk_kernel(len_ref, q_ref, k_ref, v_ref, col_ref, row_ref, s0_ref,
                  o_ref, sout_ref, s_scr, *, size: int):
    bi, ci = pl.program_id(0), pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        s_scr[...] = s0_ref[...]

    @pl.when(ci * size < len_ref[bi])
    def _():
        col = col_ref[...]  # (C, 2): the running sum of g, beta
        o, s = _chunk_math(
            q_ref[...].astype(_F32), k_ref[...].astype(_F32),
            v_ref[...].astype(_F32), col[:, 0:1], row_ref[...], col[:, 1:2],
            s_scr[...], size,
        )
        o_ref[...] = o.astype(o_ref.dtype)
        s_scr[...] = s

    @pl.when(ci * size >= len_ref[bi])
    def _():  # a chunk of padding: nothing reads these rows' values
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _():
        sout_ref[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def _chunk_launch(q, k, v, g, beta, state0, lens, *, block_t, interpret):
    bsz, length, hv, dv = v.shape
    hk, dk = k.shape[2], k.shape[3]
    rep = hv // hk
    size, padded = _chunking(block_t, length)
    n = padded // size

    def heads_first(x):  # (B, L, H, D) -> (B, H, padded, D)
        x = jnp.pad(x, ((0, 0), (0, padded - length), (0, 0), (0, 0)))
        return jnp.swapaxes(x, 1, 2)

    gcum = jnp.cumsum(
        heads_first(g[..., None]).reshape(bsz, hv, n, size), axis=-1
    )
    col = jnp.concatenate(
        [gcum.reshape(bsz, hv, padded, 1), heads_first(beta[..., None])],
        axis=-1,
    )
    key_rows = lambda b, h, c, lens: (b, h // rep, c, 0)  # noqa: E731
    rows = lambda b, h, c, lens: (b, h, c, 0)  # noqa: E731
    state = lambda b, h, c, lens: (b, h, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, hv, n),
        in_specs=[
            pl.BlockSpec((None, None, size, dk), key_rows),  # q
            pl.BlockSpec((None, None, size, dk), key_rows),  # k
            pl.BlockSpec((None, None, size, dv), rows),  # v
            pl.BlockSpec((None, None, size, 2), rows),  # cumulated g, beta
            pl.BlockSpec(  # cumulated g again, as rows
                (None, None, None, 1, size),
                lambda b, h, c, lens: (b, h, c, 0, 0),
            ),
            pl.BlockSpec((None, None, dk, dv), state),  # state0
        ],
        out_specs=[
            pl.BlockSpec((None, None, size, dv), rows),
            pl.BlockSpec((None, None, dk, dv), state),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
    )
    o, s = pl.pallas_call(
        functools.partial(_chunk_kernel, size=size),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, hv, padded, dv), v.dtype),
            jax.ShapeDtypeStruct((bsz, hv, dk, dv), _F32),
        ],
        name=CHUNK_KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, heads_first(q), heads_first(k), heads_first(v), col,
      gcum[:, :, :, None, :], state0.astype(_F32))
    return jnp.swapaxes(o, 1, 2)[:, :length], s


@jax.named_scope("gdn/chunk")
def gated_delta_chunk(
    q, k, v, g, beta, state0, true_len, *,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    block_t: int = 128,
):
    """The recurrence over ``L`` tokens a row in its chunked form (module
    docstring): shapes as :func:`gated_delta_recurrence_jnp`.  Returns
    ``(o (B, L, Hv, Dv) in v.dtype, the state after true_len rows (B,
    Hv, Dk, Dv) float32)``.  ``block_t`` rows a chunk: 128 read 525 /
    1088 / 2077 / 3145 µs a call at 512 / 1024 / 2048 / 3072 rows of 32
    heads of 128 x 128 where 64 read 616 / 1219 / 2403 / 3592 (more
    arithmetic a row, but products that fill the MXU's 128 rows and half
    the grid steps; PERF.md §6, PR 36)."""
    if not resolve_use_flash(use_kernel):  # the repo's one policy: auto = TPU
        return gated_delta_chunk_jnp(
            q, k, v, g, beta, state0, true_len, block_t=block_t
        )
    g, beta, lens = _masked(g, beta, true_len)
    return _chunk_launch(
        q, k, v, g, beta, state0, lens,
        block_t=block_t, interpret=_interpret(interpret),
    )


# -- the decode kernel ------------------------------------------------------


def _update_kernel(s_ref, qt_ref, kt_ref, v_ref, decay_ref, beta_ref, o_ref,
                   sout_ref, *, bs: int, bh: int, rep: int):
    for s in range(bs):  # a slot, then a head, after another: (Dk, Dv) each
        qt, kt = qt_ref[s, 0], kt_ref[s, 0]  # (Dk, bh // rep): columns
        for h in range(bh):
            k_col = kt[:, h // rep:h // rep + 1]
            q_col = qt[:, h // rep:h // rep + 1]
            st = decay_ref[s, h:h + 1, :] * s_ref[s, h]
            d = beta_ref[s, h:h + 1, :] * (
                v_ref[s, h:h + 1, :].astype(_F32)
                - jnp.sum(st * k_col, axis=0, keepdims=True)
            )
            st = st + k_col * d
            sout_ref[s, h] = st
            o_ref[s, h:h + 1, :] = jnp.sum(
                st * q_col, axis=0, keepdims=True
            ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_s", "block_h", "interpret")
)
def _update_launch(state, q, k, v, g, beta, *, block_s, block_h, interpret):
    slots, hv, dk, dv = state.shape
    hk = k.shape[1]
    rep = hv // hk
    bs = block_s if slots % block_s == 0 else slots
    bh = block_h if hv % block_h == 0 and block_h % max(rep, 8) == 0 else hv
    nh = hv // bh

    def columns(x):  # (S, Hk, Dk) -> (S, nh, Dk, Hk / nh): a block's key heads on lanes
        x = x.astype(_F32).reshape(slots, nh, hk // nh, dk)
        return jnp.swapaxes(x, 2, 3)

    # a head's two scalars as rows over the value lanes (Mosaic
    # broadcasts along one axis at a time; 1 KB beside the head's 128 KB)
    lanes = lambda x: jnp.broadcast_to(  # noqa: E731
        x.astype(_F32)[..., None], (slots, hv, dv)
    )
    cols = lambda si, hi: (si, hi, 0, 0)  # noqa: E731
    rows = lambda si, hi: (si, hi, 0)  # noqa: E731
    o, s = pl.pallas_call(
        functools.partial(_update_kernel, bs=bs, bh=bh, rep=rep),
        grid=(slots // bs, nh),
        in_specs=[
            pl.BlockSpec((bs, bh, dk, dv), cols),  # state
            pl.BlockSpec((bs, 1, dk, bh // rep), cols),  # q columns
            pl.BlockSpec((bs, 1, dk, bh // rep), cols),  # k columns
            pl.BlockSpec((bs, bh, dv), rows),  # v
            pl.BlockSpec((bs, bh, dv), rows),  # exp(g)
            pl.BlockSpec((bs, bh, dv), rows),  # beta
        ],
        out_specs=[
            pl.BlockSpec((bs, bh, dv), rows),
            pl.BlockSpec((bs, bh, dk, dv), cols),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((slots, hv, dv), v.dtype),
            jax.ShapeDtypeStruct((slots, hv, dk, dv), _F32),
        ],
        input_output_aliases={0: 1},  # the state: in place
        name=UPDATE_KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(state, columns(q), columns(k), v, lanes(jnp.exp(g.astype(_F32))),
      lanes(beta))
    return o, s


@jax.named_scope("gdn/update")
def gated_delta_update(
    state, q, k, v, g, beta, *,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    block_s: int = 1,
    block_h: int = 16,
):
    """One token for each of ``S`` slots (module docstring): shapes as
    :func:`gated_delta_update_jnp`; ``state`` must be float32 (it is
    updated in place).  Returns ``(o (S, Hv, Dv), state_new)``."""
    if not resolve_use_flash(use_kernel):
        return gated_delta_update_jnp(state, q, k, v, g, beta)
    if state.dtype != _F32:
        raise ValueError(
            f"the recurrent state must be float32, got {state.dtype}"
        )
    return _update_launch(
        state, q, k, v, g, beta,
        block_s=block_s, block_h=block_h, interpret=_interpret(interpret),
    )
