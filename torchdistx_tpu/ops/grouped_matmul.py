"""Pallas grouped matmul for routed experts: ``tdx_grouped_matmul``.

An expert layer that drops no token has as many rows as the router made
choices (``tokens x top_k``), unevenly spread over the experts.  Sorted
by expert they form *groups* of consecutive rows, each multiplied by its
own expert's matrix: ``out[r] = lhs[r] @ rhs[group(r)]``.  Work is
proportional to the rows, not to ``tokens x experts`` as the dense
compute of ``nn/moe.py`` is, and an expert no token chose is never
touched.

Layout (``plan_groups``): every group is padded up to a whole number of
row tiles of ``tm`` rows, so a tile belongs to ONE group and the kernel
is a plain tiled matmul whose right-hand block is picked by a
scalar-prefetched ``tile_group[i]``.  The number of tiles is static
(``ceil(rows / tm) + min(groups, rows)``: every non-empty group can end
in one partial tile); tiles past the last real one are *dead*: their
index maps fold onto the last real tile (an unchanged block index moves
no bytes) and their compute is skipped.  The grid runs the row tiles
innermost, so consecutive tiles of one group reuse the resident weight
block: each weight block of a group with rows is read once per call,
and the weights of an empty group never.

``swiglu=True`` takes two right-hand stacks (gate, up) and writes
``silu(lhs @ gate) * (lhs @ up)``: the SwiGLU's two matmuls share the
row tile and the intermediate pair never reaches HBM.

``use_kernel=None`` is the repo's kernel convention (as
``resolve_use_flash``): the kernel on a TPU, elsewhere the jnp path over
the same layout (a batched matmul of the tiles against their gathered
blocks: same sums).  ``use_kernel=True`` off-TPU runs the kernel in
interpret mode (``interpret=None`` -> auto): exact, slow, what the CPU
tests compare the jnp path with.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_use_flash

__all__ = ["GroupPlan", "plan_groups", "grouped_matmul", "row_tile"]

KERNEL_NAME = "tdx_grouped_matmul"


class GroupPlan(NamedTuple):
    """Where the ``rows`` (token, choice) pairs sit in the padded layout.

    ``src`` (padded_rows,): for every padded row the flat (token *
    top_k + choice) pair it holds — dead rows name pair 0, their results
    are never read.  ``dest`` (rows,): the padded row of every flat
    pair.  ``tile_group`` (tiles,): the group of every row tile (dead
    tiles repeat the last real tile's).  ``n_tiles`` (1,): the real
    tiles.  ``groups``: how many groups have at least one row."""

    src: jax.Array
    dest: jax.Array
    tile_group: jax.Array
    n_tiles: jax.Array
    groups: jax.Array
    tm: int


def row_tile(rows: int, dtype) -> int:
    """Rows a tile: large enough to feed the MXU where the groups are
    long (a prefill), the sublane packing of the dtype where they are a
    row or two (a decode step, where the weights' bytes are the cost)."""
    least = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    for tm in (256, 128, 64, 32):
        if rows >= 32 * tm:
            return tm
    return least


def _col_tile(n: int, cap: int) -> int:
    """Columns a block: the widest whole number of 128-lane tiles that
    divides ``n`` and stays under ``cap``; a narrow or odd ``n`` whole."""
    for tn in range(min(cap, n) // 128 * 128, 0, -128):
        if n % tn == 0:
            return tn
    return n


def plan_groups(
    group_ids: jax.Array, n_groups: int, tm: int, absent: bool = False
) -> GroupPlan:
    """``group_ids`` (rows,) int32 in ``[0, n_groups)``, any order.

    ``absent=True`` (an expert layer that holds a share of its experts,
    ``nn/moe.py``): an id of ``n_groups`` marks a row whose group is not
    here.  Such a row sorts last, joins no tile and reads no weight; its
    ``dest`` is row 0, whose value the caller must not use.  The tiles
    it would have filled are dead ones.  With no row here at all one
    tile of garbage is still computed (the kernel's index maps need a
    last real tile)."""
    rows = group_ids.shape[0]
    tiles = -(-rows // tm) + min(n_groups, rows)
    order = jnp.argsort(group_ids, stable=True).astype(jnp.int32)
    if absent:
        sizes = jnp.zeros((n_groups + 1,), jnp.int32).at[group_ids].add(1)
        sizes = sizes[:n_groups]
    else:
        sizes = jnp.zeros((n_groups,), jnp.int32).at[group_ids].add(1)
    padded = (sizes + tm - 1) // tm * tm
    pad_end = jnp.cumsum(padded)
    pad_start = pad_end - padded
    start = jnp.cumsum(sizes) - sizes
    n_tiles = pad_end[-1] // tm
    if absent:
        n_tiles = jnp.maximum(n_tiles, 1)
    # tile -> group: the group whose padded range holds the tile's first
    # row; dead tiles take the last real tile's group
    first_row = jnp.minimum(jnp.arange(tiles), n_tiles - 1) * tm
    tile_group = jnp.searchsorted(pad_end, first_row, side="right").astype(
        jnp.int32
    )
    if absent:
        tile_group = jnp.minimum(tile_group, n_groups - 1)
    # padded row -> sorted position (dead rows: position 0)
    r = jnp.arange(tiles * tm)
    g = jnp.repeat(tile_group, tm)
    off = r - pad_start[g]
    live = (off < sizes[g]) & (r < pad_end[-1])
    src = order[jnp.where(live, start[g] + off, 0)]
    # flat pair -> padded row
    sorted_g = group_ids[order]
    dest_sorted = pad_start[sorted_g] + (jnp.arange(rows) - start[sorted_g])
    if absent:
        dest_sorted = jnp.where(sorted_g < n_groups, dest_sorted, 0)
    dest = jnp.zeros((rows,), jnp.int32).at[order].set(
        dest_sorted.astype(jnp.int32)
    )
    return GroupPlan(
        src, dest, tile_group, n_tiles.reshape(1).astype(jnp.int32),
        jnp.sum(sizes > 0).astype(jnp.int32), tm,
    )


def _kernel(tg_ref, nt_ref, lhs_ref, *refs, swiglu: bool):
    del tg_ref  # read by the index maps
    i = pl.program_id(1)

    @pl.when(i < nt_ref[0])
    def _():
        x = lhs_ref[...]
        if swiglu:
            g_ref, u_ref, o_ref = refs
            gate = jnp.dot(x, g_ref[...], preferred_element_type=jnp.float32)
            up = jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)
            o_ref[...] = (jax.nn.silu(gate) * up).astype(o_ref.dtype)
        else:
            w_ref, o_ref = refs
            o_ref[...] = jnp.dot(
                x, w_ref[...], preferred_element_type=jnp.float32
            ).astype(o_ref.dtype)


@jax.named_scope("grouped_matmul")
def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    plan: GroupPlan,
    *,
    rhs_up: Optional[jax.Array] = None,
    block_n: int = 256,
    interpret: Optional[bool] = None,
    use_kernel: Optional[bool] = None,
) -> jax.Array:
    """``lhs`` (padded_rows, K) in ``plan``'s layout, ``rhs`` (G, K, N):
    row tile ``i`` times ``rhs[plan.tile_group[i]]`` -> (padded_rows, N).
    With ``rhs_up`` (G, K, N) the result is ``silu(lhs @ rhs) * (lhs @
    rhs_up)``.  Dead rows hold whatever was there: read nothing of
    them."""
    m, k = lhs.shape
    g, k2, n = rhs.shape
    tm = plan.tm
    if k2 != k or m != plan.tile_group.shape[0] * tm:
        raise ValueError(
            f"lhs {lhs.shape} / rhs {rhs.shape} do not fit the plan "
            f"({plan.tile_group.shape[0]} tiles of {tm} rows)"
        )
    swiglu = rhs_up is not None
    if swiglu and rhs_up.shape != rhs.shape:
        raise ValueError(f"gate {rhs.shape} and up {rhs_up.shape} differ")
    if not resolve_use_flash(use_kernel):  # the repo's one policy: auto = TPU
        tiles = lhs.reshape(-1, tm, k)
        out = jnp.einsum(
            "tmk,tkn->tmn", tiles, rhs[plan.tile_group],
            preferred_element_type=jnp.float32,
        )
        if swiglu:
            out = jax.nn.silu(out) * jnp.einsum(
                "tmk,tkn->tmn", tiles, rhs_up[plan.tile_group],
                preferred_element_type=jnp.float32,
            )
        return out.astype(lhs.dtype).reshape(m, n)
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    tn = _col_tile(n, block_n)
    n_row_tiles = m // tm

    def live(i, nt_ref):
        return jnp.minimum(i, nt_ref[0] - 1)

    def lhs_index(j, i, tg_ref, nt_ref):
        return (live(i, nt_ref), 0)

    def rhs_index(j, i, tg_ref, nt_ref):
        return (tg_ref[i], 0, j)

    def out_index(j, i, tg_ref, nt_ref):
        return (live(i, nt_ref), j)

    w_spec = pl.BlockSpec((None, k, tn), rhs_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // tn, n_row_tiles),
        in_specs=[pl.BlockSpec((tm, k), lhs_index), w_spec]
        + ([w_spec] if swiglu else []),
        out_specs=pl.BlockSpec((tm, tn), out_index),
    )
    return pl.pallas_call(
        functools.partial(_kernel, swiglu=swiglu),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        name=KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(plan.tile_group, plan.n_tiles, lhs, rhs, *([rhs_up] if swiglu else []))
