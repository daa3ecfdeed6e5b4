"""Pallas grouped matmul for routed experts: ``tdx_grouped_matmul``.

An expert layer that drops no token has as many rows as the router made
choices of the experts it holds (``tokens x top_k`` where it holds them
all), unevenly spread over the experts.  Laid out by expert they form
*groups* of consecutive rows, each multiplied by its own expert's
matrix: ``out[r] = lhs[r] @ rhs[group(r)]``.  Work is proportional to
the rows, not to ``tokens x experts`` as the dense compute of
``nn/moe.py`` is, and an expert no token chose is never touched.

Layout (``plan_groups``): every group is padded up to a whole number of
row tiles of ``tm`` rows, so a tile belongs to ONE group and the kernel
is a plain tiled matmul whose right-hand block is picked by a
scalar-prefetched ``tile_group[i]``.  The number of tiles is static:
by default ``ceil(rows / tm) + min(groups, rows)`` (every non-empty
group can end in one partial tile), enough for any ids; a caller whose
rows are mostly held elsewhere passes ``tiles = cap / tm + groups`` for
the ``cap`` rows it expects at most here and checks the count of rows
here against ``cap`` before it uses the plan (``nn/moe.py`` falls back
to the default under ``lax.cond``).  A row's place is its group's start
plus its rank in the group, both counted over the ``(rows, groups)``
comparison of ids and groups (no sort, no gather of single elements: the
chip runs those an element at a time); the one indexed operation is the
scatter of the row ids that inverts ``dest`` into ``src``.  Tiles past
the last real one are *dead*: their index maps fold onto the last real
tile (an unchanged block index moves no bytes) and their compute is
skipped.  The grid runs the row tiles innermost, so consecutive tiles of
one group reuse the resident weight block: each weight block of a group
with rows is read once per call, and the weights of an empty group
never.

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
    pair; ``padded_rows``, one past the layout, for a pair whose group
    is not here.  ``tile_group`` (tiles,): the group of every row tile (dead
    tiles repeat the last real tile's).  ``n_tiles`` (1,): the real
    tiles.  ``groups``: how many groups have at least one row."""

    src: jax.Array
    dest: jax.Array
    tile_group: jax.Array
    n_tiles: jax.Array
    groups: jax.Array
    tm: int


#: (rows a tile, least rows an even router sends ONE group): one expert
#: layer alone on the chip (``scripts/bench_expert_layer.py --row-tile``,
#: PR 37), us a layer at 32 / 64 / 128 / 256 rows a tile.  128 experts
#: of 512 held of 512 (a layout of twice the expected rows): 10 rows a
#: group 1386 / 1448 / 1576 / -, 20: 1601 / 1624 / 1862 / -, 40: 2332 /
#: 1947 / 2157 / -, 60: 2918 / 2573 / 3260 / -.  128 experts of 768, all
#: held: 48 rows a group - / 2188 / 2295 / 2975, 96: - / 3106 / 3082 /
#: 3530, 192: - / 5105 / 4930 / 4998, 288: - / 6968 / 6706 / 6933.  A
#: larger tile feeds the MXU more rows a weight block and pads every
#: group's end with more rows that are gathered and computed for nothing
_ROW_TILES = ((128, 96), (64, 32), (32, 8))


def row_tile(rows: int, n_groups: int, dtype) -> int:
    """Rows a tile, by the rows an even router sends ONE group (``rows /
    n_groups``): large enough to feed the MXU where the groups are long
    (a prefill), the sublane packing of the dtype where they are a row
    or two (a decode step, where the weights' bytes are the cost)."""
    for tm, least in _ROW_TILES:
        if rows >= least * n_groups:
            return tm
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _col_tile(n: int, cap: int) -> int:
    """Columns a block: the widest whole number of 128-lane tiles that
    divides ``n`` and stays under ``cap``; a narrow or odd ``n`` whole."""
    for tn in range(min(cap, n) // 128 * 128, 0, -128):
        if n % tn == 0:
            return tn
    return n


#: rows a block of ``_running_count``: 128 read 60 us at 20,480 x 128,
#: 256 and 512 read 76 and 74, ``jnp.cumsum`` (a reduce-window) 186
#: (chip runs, PR 37)
_COUNT_BLOCK = 128


def _running_count(member: jax.Array) -> jax.Array:
    """Inclusive count down the rows of a ``(rows, groups)`` boolean
    matrix, int32: within blocks of ``_COUNT_BLOCK`` rows a lower
    triangle of ones times the block on the MXU (0 / 1 in bfloat16,
    sums of at most a block's rows in float32: exact), plus the running
    total of the blocks before."""
    rows, g = member.shape
    b = _COUNT_BLOCK
    blocks = jnp.pad(member, ((0, -rows % b), (0, 0))).reshape(-1, b, g)
    tri = jnp.tril(jnp.ones((b, b), jnp.bfloat16))
    inner = jnp.einsum(
        "ij,bjg->big", tri, blocks.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    before = jnp.cumsum(inner[:, -1], axis=0) - inner[:, -1]
    return (inner + before[:, None]).reshape(-1, g)[:rows]


def plan_groups(
    group_ids: jax.Array, n_groups: int, tm: int, tiles: Optional[int] = None
) -> GroupPlan:
    """``group_ids`` (rows,) int32, any order.  An id outside ``[0,
    n_groups)`` marks a row whose group is not here (an expert layer
    that holds a share of its experts, ``nn/moe.py``): it joins no tile
    and reads no weight, and its ``dest`` is ``padded_rows``, one past
    the layout (a scatter there drops, a gather there fills).

    ``tiles`` (static): the tiles of the layout, by default enough for
    any ``group_ids``.  A caller that passes fewer must know that the
    rows here fit (``rows_here / tm + n_groups`` tiles always do) and
    not use the plan otherwise.  With no row here at all one tile of
    garbage is still computed (the kernel's index maps need a last real
    tile).

    Built by counting over the ``(rows, n_groups)`` comparison of ids
    and groups, which the chip does a vector at a time; the one
    operation an element at a time is the scatter that inverts ``dest``
    into ``src``."""
    rows = group_ids.shape[0]
    if tiles is None:
        tiles = -(-rows // tm) + min(n_groups, rows)
    padded_rows = tiles * tm
    member = group_ids[:, None] == jnp.arange(n_groups, dtype=jnp.int32)
    count = _running_count(member)  # a row's rank in its group, plus one
    sizes = count[-1]
    padded = (sizes + tm - 1) // tm * tm
    pad_end = jnp.cumsum(padded)
    pad_start = pad_end - padded
    n_tiles = jnp.maximum(pad_end[-1] // tm, 1)
    # tile -> group: the group whose padded range holds the tile's first
    # row, as a count of the ranges that end at or before it; dead tiles
    # take the last real tile's group
    first_row = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32), n_tiles - 1) * tm
    tile_group = jnp.sum(pad_end[None, :] <= first_row[:, None], axis=1)
    tile_group = jnp.minimum(tile_group, n_groups - 1).astype(jnp.int32)
    # flat pair -> padded row: its group's start plus its rank there
    dest = jnp.sum(jnp.where(member, pad_start[None, :] + count - 1, 0), axis=1)
    here = (group_ids >= 0) & (group_ids < n_groups)
    dest = jnp.where(here, dest, padded_rows).astype(jnp.int32)
    # padded row -> flat pair (dead rows: pair 0)
    src = jnp.zeros((padded_rows,), jnp.int32).at[dest].set(
        jnp.arange(rows, dtype=jnp.int32), mode="drop"
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
