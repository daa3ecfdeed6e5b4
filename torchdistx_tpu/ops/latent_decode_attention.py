"""Pallas absorbed latent (MLA) decode attention: ``tdx_latent_decode_attention``.

Multi-head latent attention caches ONE row a token and layer: the
compressed key/value ``c`` (``kv_lora_rank`` wide, after its norm) and
the one rope key ``k_r`` all heads share, ``[c ; k_r]``, ``R + r`` wide
(512 + 64 for the DeepSeek-V3 family).  The expanded form multiplies
every cached ``c`` up to per-head keys and values again; in a decode
step that is the whole cache through ``W_kv_b`` for one query.  The
*absorbed* form moves ``W_kv_b`` onto the query and the output instead:

    q~ = q_nope W_uk                     (H, R)    absorbed query
    s_j = (q~ . c_j + q_rope . k_r,j) * scale      one dot of [q~ ; q_rope] with row j
    o~ = sum_j softmax(s)_j c_j          (H, R)    values ARE the row's first R lanes
    o  = o~ W_uv                         (H, v)    (the caller's, after the kernel)

so the kernel is a single-"KV-head" flash-decode whose keys are the
whole row and whose values are its first ``R`` lanes: every visible row
is read ONCE for both the score and the value, and the ``H`` query heads
are the matmul's rows (32 of them: no padding up to a sublane minimum
as in the GQA kernel).

The cache is consumed as the engine stores it, ``(slots, rows, R + r)``
(``serve/kv_cache.py``), grid ``(slot, row block)``, per-slot depths
scalar-prefetched with the block pruning and DMA clamp of
``ops/decode_attention.py``.  The matmuls run in the cache's dtype with
float32 accumulation (bf16 operands on the chip: at 60 FLOPs a byte the
kernel would be compute-bound in float32), the softmax in float32.

Exactness (tests/test_latent_decode_attention.py): with one row block
the kernel follows ``jax.nn.softmax``'s op order, so in interpret mode
it matches the jnp path (:func:`latent_attend`) to <= 2 float32 ulps —
the repo's bar for a single-block kernel; across blocks the online
softmax defers the normalisation, the standard flash trade.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _shrink_block

__all__ = ["latent_decode_attention", "latent_attend"]

KERNEL_NAME = "tdx_latent_decode_attention"
_NEG_INF = -1e30


def latent_attend(
    q: jax.Array, cache: jax.Array, positions: jax.Array, *,
    value_width: int, scale: float,
) -> jax.Array:
    """The jnp path of the same math: ``q`` (B, H, W) absorbed queries,
    ``cache`` (B, rows, W), slot ``b`` attends rows ``j <=
    positions[b]``; returns (B, H, value_width) in ``q.dtype``."""
    out_dtype = q.dtype
    dt, r = cache.dtype, value_width
    q = q.astype(dt)
    dot = functools.partial(
        jnp.einsum, "bhw,bjw->bhj", preferred_element_type=jnp.float32
    )
    # q~ . c + q_rope . k_r, the two parts apart as the kernel has them
    logits = (
        dot(q[..., :r], cache[..., :r]) + dot(q[..., r:], cache[..., r:])
    ) * scale
    visible = jnp.arange(cache.shape[1])[None, :] <= positions[:, None]
    logits = jnp.where(visible[:, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhj,bjv->bhv", probs.astype(dt), cache[..., :value_width],
        preferred_element_type=jnp.float32,
    )
    return out.astype(out_dtype)


def _kernel(pos_ref, q_ref, c_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, block_k, n_k, value_width):
    b = pl.program_id(0)
    kk = pl.program_id(1)
    pos = pos_ref[b]
    r = value_width

    def scores():
        # [q~ ; q_rope] . [c ; k_r]: the two parts contracted apart,
        # each over a whole number of its own lanes
        q, c = q_ref[...], c_ref[...]
        dims = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(
            q[:, :r], c[:, :r], dims, preferred_element_type=jnp.float32
        ) + jax.lax.dot_general(
            q[:, r:], c[:, r:], dims, preferred_element_type=jnp.float32
        )
        cols = kk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(cols <= pos, s * scale, _NEG_INF)

    def pv(p):
        return jax.lax.dot_general(
            p.astype(c_ref.dtype), c_ref[:, :r], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if n_k == 1:
        # one block holds the whole row: jax.nn.softmax's own op order
        s = scores()
        unnorm = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        probs = unnorm / jnp.sum(unnorm, axis=-1, keepdims=True)
        o_ref[...] = pv(probs).astype(o_ref.dtype)
        return

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(kk * block_k <= pos)  # blocks past the slot's depth: skipped
    def _compute():
        s = scores()
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * correction + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + pv(p)
        m_ref[...] = m_new

    @pl.when(kk == n_k - 1)
    def _emit():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@jax.named_scope("latent_decode_attention")
def latent_decode_attention(
    q: jax.Array,
    cache: jax.Array,
    positions: jax.Array,
    *,
    value_width: int,
    scale: float,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``q``: (B, H, W) absorbed queries ``[q~ ; q_rope]`` (positional
    encoding applied).  ``cache``: the engine's latent slab (B, rows, W)
    with this step's row already written.  ``positions``: (B,) int32,
    slot ``b`` attends rows ``j <= positions[b]``.  Returns ``o~``
    (B, H, value_width) in ``q.dtype``: the probability-weighted sum of
    the rows' first ``value_width`` lanes, still to go through
    ``W_uv``."""
    b, h, w = q.shape
    if cache.ndim != 3 or cache.shape[0] != b or cache.shape[2] != w:
        raise ValueError(
            f"latent cache {cache.shape} does not fit queries {q.shape}: "
            "expected (slots, rows, latent + rope width)"
        )
    if not 0 < value_width < w:
        raise ValueError(f"value_width {value_width} outside (0, {w})")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    rows = cache.shape[1]
    block_k = _shrink_block(block_k, rows)
    n_k = rows // block_k

    def c_index(bb, kk, pos_ref):
        last = jnp.minimum(pos_ref[bb], rows - 1) // block_k
        return (bb, jnp.minimum(kk, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_k),
        in_specs=[
            pl.BlockSpec((None, h, w), lambda bb, kk, _: (bb, 0, 0)),
            pl.BlockSpec((None, block_k, w), c_index),
        ],
        out_specs=pl.BlockSpec(
            (None, h, value_width), lambda bb, kk, _: (bb, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((h, value_width), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_k=block_k, n_k=n_k,
            value_width=value_width,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), q.dtype),
        name=KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(positions.astype(jnp.int32), q.astype(cache.dtype), cache)
