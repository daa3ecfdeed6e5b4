"""Collectives layer: the c10d-primitive surface the reference consumes,
expressed as XLA collectives over mesh axes.

Mapping (SURVEY §5.8 / §2.4):
  c10d allreduce            -> ``all_reduce`` (lax.psum / pmean)
  c10d broadcast            -> ``broadcast`` (masked psum from source)
  c10d isend/irecv pair     -> ``exchange`` (lax.ppermute pair — GossipGraD's
                               2-peer exchange maps exactly onto a
                               CollectivePermute, gossip_grad.py:291-315)
  c10d reduce_scatter       -> ``reduce_scatter`` (lax.psum_scatter)
  c10d all_gather           -> ``all_gather`` (lax.all_gather)
  dist.new_subgroups        -> a mesh axis (parallel.mesh)
  dist.barrier              -> unnecessary under SPMD/XLA scheduling

These functions are *collective-inside-computation*: they must run inside a
``shard_map`` (or pmap) region over the named axis.  Pytree-valued inputs
are supported everywhere, since gradient pytrees are the common operand.

Every function is an audit choke point: when an ``obs.comm.comm_audit``
profile is active on the tracing thread, the call records its op count
and analytic payload/wire bytes per axis (a no-op otherwise — one
thread-local read).  The custom-VJP pairs also record their *backward*
collectives, which are Python traced under vjp; plain psum transposes
are jaxpr-level and out of audit scope (see obs/comm.py).
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.comm import record_collective as _record

__all__ = [
    "all_reduce",
    "all_mean",
    "broadcast",
    "exchange",
    "shift",
    "all_gather",
    "reduce_scatter",
    "allreduce_linear",
    "copy_psum_grad",
    "axis_index",
    "axis_size",
]


def all_reduce(tree: Any, axis: str) -> Any:
    """Sum over the mesh axis (c10d allreduce / NCCL AllReduce analog)."""
    _record("all_reduce", axis, tree)
    return jax.tree_util.tree_map(lambda x: lax.psum(x, axis), tree)


def all_mean(tree: Any, axis: str) -> Any:
    """Mean over the mesh axis (the reference's default allreduce hook
    divides by world size, FSDP default.allreduce_hook)."""
    _record("all_mean", axis, tree)
    return jax.tree_util.tree_map(lambda x: lax.pmean(x, axis), tree)


def broadcast(tree: Any, axis: str, source: int = 0) -> Any:
    """Broadcast ``source``'s value to all members of the axis.

    XLA has no first-class broadcast inside SPMD computations; the idiomatic
    lowering is mask-and-psum, which XLA recognizes and turns into an
    efficient collective.
    """
    _record("broadcast", axis, tree)
    idx = lax.axis_index(axis)

    def bc(x):
        masked = jnp.where(idx == source, x, jnp.zeros_like(x))
        return lax.psum(masked, axis)

    return jax.tree_util.tree_map(bc, tree)


def exchange(
    tree: Any,
    axis: str,
    send_to: Sequence[int],
    recv_from: Sequence[int],
    *,
    fill: str = "self",
) -> Any:
    """Point-to-point exchange: member i sends its value to ``send_to[i]``
    and receives from ``recv_from[i]`` (the batch_isend_irecv analog).

    ``send_to`` defines the CollectivePermute; ``recv_from`` is accepted for
    API parity with the reference's peer bookkeeping and validated against
    it.  A member with no incoming edge (nobody sends to it — the
    reference's INVALID_PEER skip, gossip_grad.py:18-23,273-276) keeps its
    OWN value (``fill="self"``, the safe no-op-exchange default) rather
    than the raw CollectivePermute zeros, which look like data to callers
    that forget to mask.  ``fill="zero"`` restores the raw semantics for
    callers that carry their own validity table (gossip_grad masks every
    lane itself).
    """
    if fill not in ("self", "zero"):
        raise ValueError(f"fill must be 'self' or 'zero', got {fill!r}")
    perm = [(i, int(d)) for i, d in enumerate(send_to) if int(d) >= 0]
    _record(
        "exchange", axis, tree,
        axis_size=len(send_to), senders=len(perm),
    )
    if recv_from is not None:
        implied = {dst: src for src, dst in perm}
        for i, src in enumerate(recv_from):
            if int(src) >= 0 and implied.get(i, None) != int(src):
                raise ValueError(
                    f"inconsistent peer lists: member {i} expects to receive "
                    f"from {src} but the send permutation delivers "
                    f"{implied.get(i)}"
                )
    receivers = {dst for _, dst in perm}
    if fill == "zero" or len(receivers) == len(send_to):
        return jax.tree_util.tree_map(
            lambda x: lax.ppermute(x, axis, perm), tree
        )
    # static mask of members with an incoming edge, indexed by the traced
    # axis position
    has_incoming = jnp.asarray(
        [i in receivers for i in range(len(send_to))]
    )[lax.axis_index(axis)]
    return jax.tree_util.tree_map(
        lambda x: jnp.where(has_incoming, lax.ppermute(x, axis, perm), x),
        tree,
    )


def shift(tree: Any, axis: str, offset: int = 1) -> Any:
    """Ring shift by ``offset`` (the ring-collective building block)."""
    n = lax.axis_size(axis)
    _record("shift", axis, tree, axis_size=n, senders=n)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return jax.tree_util.tree_map(lambda x: lax.ppermute(x, axis, perm), tree)


def all_gather(tree: Any, axis: str, tiled_axis: int = 0) -> Any:
    from ..obs.comm import current_comm_profile, tree_bytes

    if current_comm_profile() is not None:
        # payload is the GATHERED size (audit convention, obs/comm.py);
        # the operand here is the local shard
        n = lax.axis_size(axis)
        _record(
            "all_gather", axis,
            payload_bytes=tree_bytes(tree) * n, axis_size=n,
        )
    return jax.tree_util.tree_map(
        lambda x: lax.all_gather(x, axis, axis=tiled_axis, tiled=True), tree
    )


def reduce_scatter(tree: Any, axis: str, scatter_axis: int = 0) -> Any:
    _record("reduce_scatter", axis, tree)
    return jax.tree_util.tree_map(
        lambda x: lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True),
        tree,
    )


def allreduce_linear(tree: Any, axis: str) -> Any:
    """All-reduce whose BACKWARD is identity — Megatron's ``g`` operator,
    placed after a row-parallel matmul.

    Needed because under ``shard_map(..., check_vma=False)`` JAX cannot
    prove the cotangent is axis-replicated, so a plain ``lax.psum``
    transposes to another ``psum`` and grads upstream of the reduction
    come back multiplied by the axis size.  Mathematically the VJP of an
    all-reduce applied to a replicated cotangent IS identity; this
    custom_vjp states that.
    """

    @jax.custom_vjp
    def g(x):
        _record("allreduce_linear", axis, x)
        return lax.psum(x, axis)

    def g_fwd(x):
        _record("allreduce_linear", axis, x)
        return lax.psum(x, axis), None

    def g_bwd(_, ct):
        # identity backward: zero wire traffic, recorded so audits show
        # the op was traversed (kind's wire ratio is 0)
        _record("allreduce_linear_bwd", axis, ct)
        return (ct,)

    g.defvjp(g_fwd, g_bwd)
    return jax.tree_util.tree_map(g, tree)


def copy_psum_grad(tree: Any, axis: str) -> Any:
    """Identity whose BACKWARD is an all-reduce — Megatron's ``f``
    operator, placed where a replicated activation ENTERS a
    tensor-parallel region: each rank's backward produces only its
    shard's contribution to the input gradient, and the psum restores the
    full (replicated) cotangent."""

    @jax.custom_vjp
    def f(x):
        return x

    def f_fwd(x):
        return x, None

    def f_bwd(_, ct):
        _record("copy_psum_grad_bwd", axis, ct)
        return (lax.psum(ct, axis),)

    f.defvjp(f_fwd, f_bwd)
    return jax.tree_util.tree_map(f, tree)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    return lax.axis_size(axis)
