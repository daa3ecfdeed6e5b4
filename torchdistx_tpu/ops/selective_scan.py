"""Pallas selective scan (Mamba-1): ``tdx_selective_scan`` for a prefill,
``tdx_selective_state_update`` for a decode step.

A Mamba-1 mixer's recurrence, per channel ``c`` of ``d_inner`` and state
index ``n`` of ``d_state`` (``models/jamba.py`` has the whole layer):

    h_t[n, c] = exp(D_t[c] * A[n, c]) * h_{t-1}[n, c] + D_t[c] * B_t[n] * x_t[c]
    y_t[c]    = sum_n C_t[n] * h_t[n, c] + Dskip[c] * x_t[c]
    out_t[c]  = y_t[c] * silu(z_t[c])

``D_t`` (the step size, after its softplus), ``B_t`` and ``C_t`` depend
on the token: the recurrence is linear in ``h`` but its coefficients are
not constant, so it is neither a convolution nor one matmul.  Plain
``jax.numpy`` either materializes ``(L, d_inner, d_state)`` float32 for
an associative scan (335 MB a layer at L = 1024, 5120 x 16) or runs
``L`` tiny steps; a decode step reads and writes the whole state of
every slot (327,680 B a slot and layer at 5120 x 16 float32).

**Layout: channels on lanes.**  The state is ``(…, d_state, d_inner)``
-- 16 sublanes x ``d_inner`` lanes -- here, in the model's cache entry
and in the serve engine's slab (``serve/kv_cache.py``): a ``(…, 16)``
minor axis would fill an eighth of every vector register.  ``A`` is
taken transposed to match, ``(d_state, d_inner)``.  ``B_t`` and ``C_t``
are handed to the kernels as ``(…, d_state, 1)`` columns, so that a
step's ``B_t[n]`` is a lane broadcast of what was loaded and never a
transpose.

``tdx_selective_scan``: grid ``(batch, channel blocks, time chunks)``,
the time chunks innermost and sequential, the state of one channel block
resident in VMEM across them (float32).  A chunk first forms ``D * x``
and the masked ``D`` for all its rows at once, then runs its rows one
after another (only the recurrence itself is sequential), then applies
``Dskip`` and the gate to the chunk's rows at once.  ``true_len``
(scalar-prefetched, one a batch row) is how many leading rows are real:
rows at and past it leave the state untouched (``D`` forced to 0: decay
1, update 0) and chunks wholly past it are skipped, so that a prompt
right-padded to a bucket writes the state after its last REAL token.
The initial state is an operand (zeros for a fresh prompt).

``tdx_selective_state_update``: one token for each of ``S`` slots, grid
``(slot blocks, channel blocks)``; the state is read, updated and
written IN PLACE (``input_output_aliases``: the serve engine's slab is
donated to its programs, as the KV arrays are), fused with the ``Dskip``
term and the gate.

Each kernel stands beside a ``jax.numpy`` form of the same arithmetic in
the same order (``selective_scan_jnp``, ``selective_state_update_jnp``):
the path off the chip (``use_kernel=None`` is the repo's convention:
the kernel on a TPU) and the oracle of ``tests/test_selective_scan.py``,
which runs the kernels in interpret mode against them.
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
from .grouped_matmul import _col_tile as _lane_block  # widest dividing lane tiles

__all__ = [
    "selective_scan",
    "selective_scan_jnp",
    "selective_state_update",
    "selective_state_update_jnp",
]

SCAN_KERNEL_NAME = "tdx_selective_scan"
UPDATE_KERNEL_NAME = "tdx_selective_state_update"

_F32 = jnp.float32


def _gate(y, z):
    return y * (z * jax.nn.sigmoid(z))


# -- the jnp forms: the path off the chip, and the tests' oracle ------------


def selective_state_update_jnp(h, x, dt, a, b, c, dskip, z):
    """One token a row.  ``h`` (S, N, C) float32; ``x``, ``z`` (S, C);
    ``dt`` (S, C) float32; ``a`` (N, C); ``b``, ``c`` (S, N); ``dskip``
    (C,).  Returns ``(out (S, C) in x.dtype, h_new (S, N, C))``."""
    xf, zf = x.astype(_F32), z.astype(_F32)
    dt = dt.astype(_F32)
    decay = jnp.exp(dt[:, None, :] * a[None].astype(_F32))
    h = decay * h + (dt * xf)[:, None, :] * b.astype(_F32)[:, :, None]
    y = jnp.sum(h * c.astype(_F32)[:, :, None], axis=1)
    y = y + dskip.astype(_F32)[None] * xf
    return _gate(y, zf).astype(x.dtype), h


def selective_scan_jnp(x, dt, a, b, c, dskip, z, h0, true_len):
    """``L`` tokens a row, one after another (a ``lax.scan`` of
    :func:`selective_state_update_jnp`).  ``x``, ``z`` (B, L, C); ``dt``
    (B, L, C) float32; ``b``, ``c`` (B, L, N); ``h0`` (B, N, C);
    ``true_len`` a scalar or (B,): rows at and past it leave the state
    as it is.  Returns ``(out (B, L, C), h after true_len rows)``."""
    bsz, length, _ = x.shape
    lens = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32), (bsz,))

    def step(h, row):
        t, x_t, dt_t, b_t, c_t, z_t = row
        dt_t = jnp.where((t < lens)[:, None], dt_t.astype(_F32), 0.0)
        out, h = selective_state_update_jnp(h, x_t, dt_t, a, b_t, c_t, dskip, z_t)
        return h, out

    rows = (jnp.arange(length),) + tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c, z)
    )
    h, out = lax.scan(step, h0.astype(_F32), rows)
    return jnp.moveaxis(out, 0, 1), h


# -- the prefill kernel -----------------------------------------------------


def _scan_kernel(
    len_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, dskip_ref, z_ref, h0_ref,
    y_ref, hout_ref, h_scr, dt_scr, dtx_scr, y_scr, *, tc: int,
):
    bi, ti = pl.program_id(0), pl.program_id(2)
    true_len = len_ref[bi]
    start = ti * tc

    @pl.when(ti == 0)
    def _():
        h_scr[...] = h0_ref[0]

    @pl.when(start < true_len)
    def _():
        x = x_ref[0].astype(_F32)  # (tc, bc)
        row = start + lax.broadcasted_iota(jnp.int32, x.shape, 0)
        dt = jnp.where(row < true_len, dt_ref[0], 0.0)
        dt_scr[...] = dt
        dtx_scr[...] = dt * x
        a = a_ref[...]  # (N, bc)

        def step(t, h):
            dt_t = dt_scr[pl.ds(t, 1), :]  # (1, bc): over the sublanes
            h = jnp.exp(dt_t * a) * h + dtx_scr[pl.ds(t, 1), :] * b_ref[0, t]
            y_scr[pl.ds(t, 1), :] = jnp.sum(
                h * c_ref[0, t], axis=0, keepdims=True
            )
            return h

        def eight(g, h):  # unrolled by hand: Mosaic unrolls all or nothing
            for i in range(8):
                h = step(g * 8 + i, h)
            return h

        h_scr[...] = lax.fori_loop(0, tc // 8, eight, h_scr[...])
        zf = z_ref[0].astype(_F32)
        y_ref[0] = _gate(y_scr[...] + dskip_ref[...] * x, zf).astype(y_ref.dtype)

    @pl.when(start >= true_len)
    def _():  # a chunk of padding: nothing reads these rows' values
        y_ref[0] = jnp.zeros(y_ref.shape[1:], y_ref.dtype)

    @pl.when(ti == pl.num_programs(2) - 1)
    def _():
        hout_ref[0] = h_scr[...]


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_t", "interpret")
)
def _scan_launch(x, dt, a, b, c, dskip, z, h0, lens, *, block_c, block_t,
                 interpret):
    bsz, length, ch = x.shape
    n = a.shape[0]
    tc = min(block_t, -(-length // 8) * 8)
    padded = -(-length // tc) * tc
    if padded != length:  # rows past true_len: masked, then cut off
        grow = lambda v: jnp.pad(  # noqa: E731
            v, ((0, 0), (0, padded - length)) + ((0, 0),) * (v.ndim - 2)
        )
        x, dt, b, c, z = (grow(v) for v in (x, dt, b, c, z))
    bc = _lane_block(ch, block_c)
    rows = lambda bi, ci, ti, lens: (bi, ti, ci)  # noqa: E731
    cols = lambda bi, ci, ti, lens: (bi, ti, 0, 0)  # noqa: E731
    state = lambda bi, ci, ti, lens: (bi, 0, ci)  # noqa: E731
    chan = lambda bi, ci, ti, lens: (0, ci)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, ch // bc, padded // tc),
        in_specs=[
            pl.BlockSpec((1, tc, bc), rows),  # x
            pl.BlockSpec((1, tc, bc), rows),  # dt
            pl.BlockSpec((n, bc), chan),  # a
            pl.BlockSpec((1, tc, n, 1), cols),  # b
            pl.BlockSpec((1, tc, n, 1), cols),  # c
            pl.BlockSpec((1, bc), chan),  # dskip
            pl.BlockSpec((1, tc, bc), rows),  # z
            pl.BlockSpec((1, n, bc), state),  # h0
        ],
        out_specs=[
            pl.BlockSpec((1, tc, bc), rows),
            pl.BlockSpec((1, n, bc), state),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, bc), _F32),
            pltpu.VMEM((tc, bc), _F32),
            pltpu.VMEM((tc, bc), _F32),
            pltpu.VMEM((tc, bc), _F32),
        ],
    )
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, tc=tc),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, padded, ch), x.dtype),
            jax.ShapeDtypeStruct((bsz, n, ch), _F32),
        ],
        name=SCAN_KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        lens, x, dt.astype(_F32), a.astype(_F32),
        b.astype(_F32)[..., None], c.astype(_F32)[..., None],
        dskip.astype(_F32)[None], z, h0.astype(_F32),
    )
    return y[:, :length], h


@jax.named_scope("mamba/scan")
def selective_scan(
    x, dt, a, b, c, dskip, z, h0, true_len, *,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    block_c: int = 1024,
    block_t: int = 128,
):
    """The recurrence over ``L`` tokens a row (module docstring): shapes
    as :func:`selective_scan_jnp`.  Returns ``(y * silu(z) (B, L, C) in
    x.dtype, the state after true_len rows (B, N, C) float32)``.
    ``block_c`` x ``block_t`` is what a grid step works: 1024 channels
    x 128 rows read 80 / 148 µs a call at 256 / 512 rows of 5120
    channels where 512 x 128 read 93 / 174 (PERF.md §6, PR 34); 1024 x
    256 no longer fits the kernel's VMEM (the ``B`` and ``C`` columns
    take 8 KB a row there)."""
    if not resolve_use_flash(use_kernel):  # the repo's one policy: auto = TPU
        return selective_scan_jnp(x, dt, a, b, c, dskip, z, h0, true_len)
    lens = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32), (x.shape[0],))
    return _scan_launch(
        x, dt, a, b, c, dskip, z, h0, lens,
        block_c=block_c, block_t=block_t, interpret=_interpret(interpret),
    )


# -- the decode kernel ------------------------------------------------------


def _update_kernel(
    h_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, dskip_ref, z_ref,
    y_ref, hout_ref, y_scr, *, bs: int,
):
    a = a_ref[...]  # (N, bc)
    x = x_ref[...].astype(_F32)  # (bs, bc)
    dt = dt_ref[...]
    dtx = dt * x
    for s in range(bs):  # a slot after another, each (N, bc)
        h = (
            jnp.exp(dt[s:s + 1] * a) * h_ref[s]
            + dtx[s:s + 1] * b_ref[s]
        )
        hout_ref[s] = h
        y_scr[s:s + 1, :] = jnp.sum(h * c_ref[s], axis=0, keepdims=True)
    zf = z_ref[...].astype(_F32)
    y_ref[...] = _gate(y_scr[...] + dskip_ref[...] * x, zf).astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_s", "block_c", "interpret")
)
def _update_launch(h, x, dt, a, b, c, dskip, z, *, block_s, block_c,
                   interpret):
    slots, n, ch = h.shape
    bs = block_s if slots % block_s == 0 else slots
    bc = _lane_block(ch, block_c)
    rows = lambda si, ci: (si, ci)  # noqa: E731
    cols = lambda si, ci: (si, 0, 0)  # noqa: E731
    state = lambda si, ci: (si, 0, ci)  # noqa: E731
    chan = lambda si, ci: (0, ci)  # noqa: E731
    y, h = pl.pallas_call(
        functools.partial(_update_kernel, bs=bs),
        grid=(slots // bs, ch // bc),
        in_specs=[
            pl.BlockSpec((bs, n, bc), state),  # h
            pl.BlockSpec((bs, bc), rows),  # x
            pl.BlockSpec((bs, bc), rows),  # dt
            pl.BlockSpec((n, bc), chan),  # a
            pl.BlockSpec((bs, n, 1), cols),  # b
            pl.BlockSpec((bs, n, 1), cols),  # c
            pl.BlockSpec((1, bc), chan),  # dskip
            pl.BlockSpec((bs, bc), rows),  # z
        ],
        out_specs=[
            pl.BlockSpec((bs, bc), rows),
            pl.BlockSpec((bs, n, bc), state),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((slots, ch), x.dtype),
            jax.ShapeDtypeStruct((slots, n, ch), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((bs, bc), _F32)],
        input_output_aliases={0: 1},  # the state: in place
        name=UPDATE_KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(
        h, x, dt.astype(_F32), a.astype(_F32),
        b.astype(_F32)[..., None], c.astype(_F32)[..., None],
        dskip.astype(_F32)[None], z,
    )
    return y, h


@jax.named_scope("mamba/update")
def selective_state_update(
    h, x, dt, a, b, c, dskip, z, *,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    block_s: int = 16,
    block_c: int = 1280,
):
    """One token for each of ``S`` slots (module docstring): shapes as
    :func:`selective_state_update_jnp`; ``h`` must be float32 (it is
    updated in place).  Returns ``(y * silu(z) (S, C), h_new)``."""
    if not resolve_use_flash(use_kernel):
        return selective_state_update_jnp(h, x, dt, a, b, c, dskip, z)
    if h.dtype != _F32:
        raise ValueError(f"the recurrent state must be float32, got {h.dtype}")
    return _update_launch(
        h, x, dt, a, b, c, dskip, z,
        block_s=block_s, block_c=block_c, interpret=_interpret(interpret),
    )
