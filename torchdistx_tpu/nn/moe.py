"""Mixture-of-Experts layer with expert parallelism.

Absent in the reference (SURVEY §2.4 marks EP absent); built GSPMD-first:
expert weights are stacked with a leading expert dim, and expert
parallelism is *a sharding annotation* — ``moe_shard_rule`` places that dim
over an ``ep`` mesh axis and XLA partitions the expert einsums and inserts
the combine reduction.  Construction goes through the interposition layer,
so MoE models deferred-init and sharded-materialize like everything else.

Routing is top-k softmax gating with renormalized weights.  Two compute
modes:

  - dense (default): every expert computes every token; the combine is
    masked.  Exact and simple, but E/top_k times the dispatched FLOPs.
  - capacity dispatch (``capacity_factor=``): the Mesh-TensorFlow /
    Switch algorithm — each expert receives at most
    ``C = ceil(tokens * top_k / E * capacity_factor)`` tokens, gathered by
    a dispatch tensor and computed as (E, C, D) batches.  FLOPs drop to
    ~``top_k/E`` of dense; tokens beyond an expert's capacity are dropped
    (their combine weight is zero), which is the standard MoE trade.

Capacity dispatch itself has two implementations (``dispatch_mode``):

  - "einsum" (default): one-hot (n, E, C) dispatch/combine tensors
    contracted against the tokens.  Under an ``ep`` sharding these
    einsums are what GSPMD partitions into all-to-alls over the expert
    axis — the TPU-native distributed token shuffle — which is why it
    stays the default.
  - "gather": the dispatch table is (E, C) token indices and the combine
    a (n, k) gather of expert outputs — O(E*C*D) data movement instead
    of the einsums' O(n*E*C*D) MACs, which at typical shapes exceed the
    expert FFN FLOPs themselves (n=4096, E=8, C=1024, D=4096: 137 GMACs
    of pure bookkeeping per layer).  Same GShard priority/drop
    discipline, same expert compute; use it when experts are local
    (single chip, or inside an explicit shard_map over ``ep``).

With ``capacity_factor >= E / top_k`` no token can be dropped and all
modes agree (tested).

A third compute path drops no token at any routing and does work
proportional to the (token, expert) rows: ``dispatch_mode="grouped"``
(no ``capacity_factor``) lays the rows out by expert and runs the
SwiGLU as grouped matmuls over the experts that have rows
(``ops/grouped_matmul.py``, the Pallas kernel ``tdx_grouped_matmul``).
The layout is built by COUNTING (``plan_groups``): a row's place is its
expert's start plus its rank among that expert's rows, both read off
the ``(rows, experts)`` comparison of choices and experts a vector at a
time; nothing is sorted.  An expert no token chose is never read; one
every token chose simply has a long group.  It is the path for serving
an expert model on one chip, where dense compute is ``E / top_k`` times
the FLOPs and a capacity that cannot drop is dense again.

The router is configurable for the DeepSeek-V3 family: ``scoring``
(``"softmax"`` | ``"sigmoid"``, the latter in float32), a learned
``selection_bias`` that enters the choice of experts and not their
weights, ``routed_scale`` on the renormalised weights, and a shared
expert (``shared_ffn_dim``) that every token takes, with
``shared_gate=True`` under a gate of its own, ``sigmoid(w_sg . x)`` (the
Qwen3-Next family).

**A layer told which experts it holds** (``held=(lo, hi)``, the grouped
path only): what one chip of an expert-parallel group has of the layer.
The router stays ``n_experts`` wide and the choice and the
renormalisation run over ALL experts, as on every other chip; the three
stacks hold experts ``lo .. hi - 1`` only, and the layer computes the
rows whose expert it holds: a choice outside the share joins no group,
sits in no row of the layout, reads no weight and adds nothing.  The
layout is sized by the rows held HERE: ``cap / tm + n_held`` tiles of
``tm`` rows, ``cap`` twice the rows an even router sends the share
(``2 * tokens * top_k * n_held / n_experts``, up to a whole tile) and
``tm`` by the rows expected a held expert (``row_tile``).  The rows held
are counted before the layout is used; a routing that sends the share
more than ``cap`` runs the layout sized by all ``tokens x top_k`` rows
instead (the other branch of one ``lax.cond``), so no token is dropped
and the result is exact for ANY router.  The result is this chip's
PARTIAL sum (plus the shared expert, which every chip computes alike):
the shares of a layer add up to the whole layer once the shared term is
counted once (``tests/test_moe.py``).  Nothing here stands in for the
other chips or for the exchange between them.

Counters: under :func:`moe_count_tape` every grouped call records the
rows it computed and the groups it touched (a traced scalar), and a
layer that holds a share also the rows it left to the others and
whether it ran the full-size layout (``overflows``, 0 or 1); the serve
programs sum them on the device (``serve/engine.py``), and
``ServeMetrics`` names the last ``moe_layout_overflows``.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from . import init
from .module import Module, Parameter
from .layers import Linear

__all__ = ["MoE", "moe_shard_rule", "moe_count_tape", "tape_totals"]


#: the column block of the grouped path's fused gate-and-up matmul, by
#: the experts' width: 384 is kanana-2-30b's sweep at 768 (two blocks a
#: stack); at 512 a block of 384 would fall to 256 (the widest dividing
#: tile)
_UP_BLOCK_N = {512: 512}


class _Tape(threading.local):
    current = None


_tape = _Tape()


@contextlib.contextmanager
def moe_count_tape():
    """While open (at trace time), every grouped expert call appends
    ``(rows, groups)``: the (token, expert) rows it computed (static) and
    the experts that had at least one (a traced int32 scalar).  A layer
    that holds a share of its experts appends ``(rows, groups,
    elsewhere, overflows)``, all traced: the rows of the experts it
    holds, the held experts touched, the rows whose expert is held
    elsewhere, and 1 where the call held more rows than its layout is
    sized for and ran the full-size one (else 0)."""
    tape: list = []
    prev, _tape.current = _tape.current, tape
    try:
        yield tape
    finally:
        _tape.current = prev


def tape_totals(tape) -> jax.Array:
    """int32 ``[rows, groups]`` summed over the tape's calls; ``[rows,
    groups, elsewhere, overflows]`` where a call recorded a share."""
    width = max((len(t) for t in tape), default=2)
    zero = jnp.zeros((), jnp.int32)
    return jnp.stack([
        sum((jnp.asarray(t[i], jnp.int32) for t in tape if len(t) > i), zero)
        for i in range(width)
    ])


class _SharedFFN(Module):
    """The SwiGLU every token takes beside its routed experts."""

    def __init__(self, dim, ffn_dim, dtype, weight_init):
        super().__init__()
        lin = lambda i, o: Linear(  # noqa: E731
            i, o, bias=False, dtype=dtype, weight_init=weight_init
        )
        self.w_gate = lin(dim, ffn_dim)
        self.w_up = lin(dim, ffn_dim)
        self.w_down = lin(ffn_dim, dim)

    def forward(self, x):
        return self.w_down(jax.nn.silu(self.w_gate(x)) * self.w_up(x))


class MoE(Module):
    """Top-k routed SwiGLU-style expert FFN.

    Expert weights: ``w_up``/``w_gate`` (E, D, F) and ``w_down`` (E, F, D).
    """

    def __init__(
        self,
        dim: int,
        ffn_dim: int,
        n_experts: int,
        top_k: int = 2,
        dtype=jnp.float32,
        capacity_factor: Optional[float] = None,
        dispatch_mode: str = "einsum",
        scoring: str = "softmax",
        selection_bias: bool = False,
        routed_scale: float = 1.0,
        shared_ffn_dim: Optional[int] = None,
        weight_init: Optional[Callable] = None,
        use_kernel: Optional[bool] = None,
        held: Optional[tuple] = None,
        shared_gate: bool = False,
    ) -> None:
        """``weight_init``: optional ``fn(shape, dtype)`` for every leaf
        (router, selection bias, expert stacks, shared expert and its
        gate), in construction order; default: the uniform fan-in bounds.
        ``use_kernel``: the grouped path's Pallas kernel (None = on a
        TPU), else its jnp form.  ``held=(lo, hi)``: the share of the
        experts this layer holds (module docstring; None = all).
        ``shared_gate``: the shared expert's own sigmoid gate."""
        super().__init__()
        if not 1 <= top_k <= n_experts:
            raise ValueError(f"top_k={top_k} out of range for {n_experts} experts")
        if dispatch_mode not in ("einsum", "gather", "grouped"):
            raise ValueError(
                f"dispatch_mode {dispatch_mode!r} (expected 'einsum', "
                "'gather' or 'grouped')"
            )
        if dispatch_mode == "gather" and capacity_factor is None:
            raise ValueError(
                "dispatch_mode='gather' requires capacity_factor: dense "
                "compute (capacity_factor=None) has no dispatch step for "
                "the gather path to replace"
            )
        if dispatch_mode == "grouped" and capacity_factor is not None:
            raise ValueError(
                "dispatch_mode='grouped' drops no token and takes no "
                "capacity_factor"
            )
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"scoring {scoring!r} (expected 'softmax' or 'sigmoid')"
            )
        if held is not None:
            lo, hi = held = (int(held[0]), int(held[1]))
            if not 0 <= lo < hi <= n_experts:
                raise ValueError(
                    f"held={held} is not a range of the {n_experts} experts"
                )
            if dispatch_mode != "grouped":
                raise ValueError(
                    "held= (a share of the experts) needs "
                    "dispatch_mode='grouped': the dense and capacity paths "
                    "compute every expert"
                )
            if held == (0, n_experts):
                held = None  # the whole layer: today's program
        if shared_gate and not shared_ffn_dim:
            raise ValueError("shared_gate=True without a shared expert")
        self.held = held
        n_held = n_experts if held is None else held[1] - held[0]
        self.dim = dim
        self.ffn_dim = ffn_dim
        self.n_experts = n_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.dispatch_mode = dispatch_mode
        self.scoring = scoring
        self.routed_scale = float(routed_scale)
        self.use_kernel = use_kernel
        self.up_block_n = _UP_BLOCK_N.get(ffn_dim, 384)
        self.router = Linear(
            dim, n_experts, bias=False, dtype=dtype, weight_init=weight_init
        )
        bound = math.sqrt(1.0 / dim)
        down_bound = math.sqrt(1.0 / ffn_dim)
        if weight_init is None:
            up_init = lambda s, d: init.uniform(s, -bound, bound, dtype=d)  # noqa: E731
            down_init = lambda s, d: init.uniform(  # noqa: E731
                s, -down_bound, down_bound, dtype=d
            )
        else:
            up_init = down_init = weight_init
        if selection_bias:
            # DeepSeek-V3's e_score_correction_bias: added to the scores
            # for the CHOICE of experts only
            self.e_score_correction_bias = Parameter(
                (weight_init or init.zeros)((n_experts,), dtype)
            )
        else:
            self.register_parameter("e_score_correction_bias", None)
        self.w_gate = Parameter(up_init((n_held, dim, ffn_dim), dtype))
        self.w_up = Parameter(up_init((n_held, dim, ffn_dim), dtype))
        self.w_down = Parameter(down_init((n_held, ffn_dim, dim), dtype))
        self.shared = (
            _SharedFFN(dim, shared_ffn_dim, dtype, weight_init)
            if shared_ffn_dim
            else None
        )
        self.shared_gate = (
            Linear(dim, 1, bias=False, dtype=dtype, weight_init=weight_init)
            if shared_gate
            else None
        )

    def _route(self, x):
        """Every expert's score, float32: softmax over the router's
        logits, or (DeepSeek-V3) their sigmoid with the logits
        accumulated in float32."""
        if self.scoring == "sigmoid":
            logits = jnp.einsum(
                "...d,ed->...e", x, self.router.weight,
                preferred_element_type=jnp.float32,
            )
            return jax.nn.sigmoid(logits)
        logits = self.router(x).astype(jnp.float32)  # (..., E)
        return jax.nn.softmax(logits, axis=-1)

    def _choose(self, probs):
        """The ``top_k`` experts of every token and their weights: the
        choice is by score plus the selection bias (where there is one),
        the weight is the chosen experts' OWN scores renormalised to sum
        to one, times ``routed_scale``."""
        bias = self.e_score_correction_bias
        if bias is None:
            top_p, top_i = jax.lax.top_k(probs, self.top_k)
        else:
            _, top_i = jax.lax.top_k(
                probs + bias.astype(jnp.float32), self.top_k
            )
            top_p = jnp.take_along_axis(probs, top_i, axis=-1)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        if self.routed_scale != 1.0:
            top_p = top_p * self.routed_scale
        return top_p, top_i

    def forward(self, x, return_aux: bool = False):
        """Apply the layer; with ``return_aux=True`` also return the
        load-balancing auxiliary loss computed from the SAME routing pass
        (no second router forward)."""
        with jax.named_scope("moe/route"):
            probs = self._route(x)
        if self.dispatch_mode == "grouped":
            y = self._grouped_forward(x, probs)
        elif self.capacity_factor is not None:
            y = self._capacity_forward(x, probs)
        else:
            y = self._dense_forward(x, probs)
        if self.shared is not None:
            with jax.named_scope("moe/shared"):
                shared = self.shared(x)
                if self.shared_gate is not None:
                    gate = self.shared_gate(x).astype(jnp.float32)
                    shared = (jax.nn.sigmoid(gate) * shared).astype(x.dtype)
                y = y + shared
        if return_aux:
            return y, self._balance_loss(probs)
        return y

    def _grouped_forward(self, x, probs):
        """No token dropped, work proportional to the (token, expert)
        rows whose expert is held here: the rows laid out by expert
        (``plan_groups``: by counting, no sort), the SwiGLU as grouped
        matmuls over the experts that have rows, each token's results
        gathered back and summed under their weights.  With a share of
        the experts (``held``) the layout is sized by the rows an even
        router sends here, twice over; the rows held are counted before
        the layout is used, and a routing that sends more runs the
        layout sized by all ``tokens x top_k`` rows instead
        (``lax.cond``): exact for any router."""
        from ..ops.grouped_matmul import row_tile

        k = self.top_k
        lead, d = x.shape[:-1], x.shape[-1]
        xf = x.reshape(-1, d)
        n = xf.shape[0]
        with jax.named_scope("moe/route"):
            top_p, top_i = self._choose(probs.reshape(n, self.n_experts))
            ids = top_i.reshape(-1).astype(jnp.int32)
        lo, hi = self.held or (0, self.n_experts)
        n_held = hi - lo
        if self.held is not None:
            # an id outside the share matches no group of the plan
            ids = jnp.where((ids >= lo) & (ids < hi), ids - lo, n_held)
            rows = jnp.sum(ids < n_held, dtype=jnp.int32)
        # the tile and the layout by the rows EXPECTED here (an even
        # router sends a share its part of the choices): room for twice
        # as many, each held expert's last tile partly filled
        expected = max(1, n * k * n_held // self.n_experts)
        tm = row_tile(expected, n_held, x.dtype)
        cap = -(-2 * expected // tm) * tm
        tiles = cap // tm + n_held
        if tiles >= -(-n * k // tm) + min(n_held, n * k):
            # every expert held, a large share or few tokens: the layout
            # of every row is no larger
            y, groups = self._grouped_experts(xf, ids, top_p, tm, None)
            overflow = jnp.zeros((), jnp.int32)
        else:
            overflow = (rows > cap).astype(jnp.int32)
            y, groups = jax.lax.cond(
                overflow,
                lambda: self._grouped_experts(xf, ids, top_p, tm, None),
                lambda: self._grouped_experts(xf, ids, top_p, tm, tiles),
            )
        if _tape.current is not None:
            _tape.current.append(
                (n * k, groups) if self.held is None
                else (rows, groups, n * k - rows, overflow)
            )
        return y.reshape(*lead, d)

    def _grouped_experts(self, xf, ids, top_p, tm, tiles):
        """The experts' SwiGLU over a layout of ``tiles`` row tiles (None:
        enough for every row) and the combine: ``(n, d)`` in ``xf``'s
        dtype, and the groups that had rows."""
        from ..ops.grouped_matmul import grouped_matmul, plan_groups

        n, d = xf.shape
        k = self.top_k
        with jax.named_scope("moe/route"):
            plan = plan_groups(ids, self.w_gate.shape[0], tm, tiles)
        with jax.named_scope("moe/experts"):
            h = grouped_matmul(
                xf[plan.src // k], self.w_gate, plan, rhs_up=self.w_up,
                block_n=self.up_block_n, use_kernel=self.use_kernel,
            )
            y = grouped_matmul(
                h, self.w_down, plan, block_n=512, use_kernel=self.use_kernel
            )
            # one gather a choice, summed as it arrives: the (n, k, d)
            # rows never reach HBM (0.36 ms against 1.15 for one gather
            # and an einsum at 2,048 x 10 rows; chip runs, PR 37).  A row
            # held elsewhere names no row: it reads zeros
            dest = plan.dest.reshape(n, k)
            out = jnp.zeros((n, d), jnp.float32)
            for j in range(k):  # static, small
                rows = y.at[dest[:, j]].get(mode="fill", fill_value=0)
                out = out + top_p[:, j, None] * rows.astype(jnp.float32)
        return out.astype(xf.dtype), plan.groups

    def _dense_forward(self, x, probs):
        top_p, top_i = self._choose(probs)
        # combine weights as a dense (..., E) mask — partition-friendly
        onehot = jax.nn.one_hot(top_i, self.n_experts, dtype=probs.dtype)
        combine = jnp.einsum("...k,...ke->...e", top_p, onehot)
        expert_out = self._dense_ffn(x)
        return jnp.einsum("...e,...ed->...d", combine.astype(x.dtype), expert_out)

    def _dense_ffn(self, x):
        """(..., D) -> (..., E, D): every expert's FFN on every token —
        the overridable compute hook of the dense path (the capacity
        path's analog is :meth:`_experts`)."""
        h_gate = jnp.einsum("...d,edf->...ef", x, self.w_gate)
        h_up = jnp.einsum("...d,edf->...ef", x, self.w_up)
        h = jax.nn.silu(h_gate) * h_up
        return jnp.einsum("...ef,efd->...ed", h, self.w_down)

    def _capacity_slots(self, pf, cap):
        """GShard slot assignment shared by both dispatch modes: for each
        of the k routing choices, the chosen expert, the token's slot in
        that expert's capacity, the keep mask, and the combine weight.
        Priority runs top-1 slots before top-2 across all tokens, then by
        token order — the standard GShard discipline."""
        e, k = self.n_experts, self.top_k
        top_p, top_i = self._choose(pf)
        slots = []
        counts = jnp.zeros((e,), jnp.int32)
        for j in range(k):  # static, small
            oh = jax.nn.one_hot(top_i[:, j], e, dtype=jnp.int32)  # (n, E)
            pos = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]  # (n, E)
            pos_t = jnp.sum(oh * pos, axis=-1)  # (n,) position in expert
            keep = pos_t < cap
            slots.append((top_i[:, j], pos_t, keep, top_p[:, j]))
            counts = counts + jnp.sum(oh, axis=0)
        return slots

    def _experts(self, expert_in):
        """(E, C, D) -> (E, C, D): the SwiGLU expert FFNs, shared by both
        dispatch modes (MXU-shaped batched matmuls)."""
        h = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", expert_in, self.w_gate)
        ) * jnp.einsum("ecd,edf->ecf", expert_in, self.w_up)
        return jnp.einsum("ecf,efd->ecd", h, self.w_down)

    def _capacity_forward(self, x, probs):
        """Capacity-based token dispatch (Mesh-TF/Switch): experts compute
        (E, C, D) gathered batches instead of every token (module
        docstring; ``dispatch_mode`` picks the implementation)."""
        e, k = self.n_experts, self.top_k
        lead = x.shape[:-1]
        d = x.shape[-1]
        xf = x.reshape(-1, d)
        pf = probs.reshape(-1, e)
        n = xf.shape[0]
        cap = int(math.ceil(n * k / e * float(self.capacity_factor)))
        cap = min(cap, n)
        slots = self._capacity_slots(pf, cap)

        if self.dispatch_mode == "gather":
            return self._capacity_gather(xf, slots, n, e, cap, lead, d)

        dispatch = jnp.zeros((n, e, cap), x.dtype)
        combine = jnp.zeros((n, e, cap), x.dtype)
        for ei, pos_t, keep, w in slots:
            oh = jax.nn.one_hot(ei, e, dtype=jnp.int32)  # (n, E)
            slot = jax.nn.one_hot(
                jnp.where(keep, pos_t, 0), cap, dtype=x.dtype
            )  # (n, C)
            sel = oh.astype(x.dtype) * keep[:, None].astype(x.dtype)
            dispatch = dispatch + sel[:, :, None] * slot[:, None, :]
            combine = combine + (
                sel * w[:, None].astype(x.dtype)
            )[:, :, None] * slot[:, None, :]

        # (n, E, C) x (n, D) -> (E, C, D): the all-to-all under ep sharding
        expert_in = jnp.einsum("nec,nd->ecd", dispatch, xf)
        expert_out = self._experts(expert_in)
        y = jnp.einsum("nec,ecd->nd", combine, expert_out)
        return y.reshape(*lead, d)

    def _capacity_gather(self, xf, slots, n, e, cap, lead, d):
        """Gather/scatter dispatch: same math as the einsum path with the
        bookkeeping MACs removed.  The dispatch table is (E*C,) token
        indices (scatter, overflow dropped via out-of-bounds index), the
        combine a per-choice gather of expert outputs weighted by the
        (zeroed-when-dropped) routing weight — empty slots carry exact
        zeros so expert compute matches the einsum path bit-for-bit."""
        dtype = xf.dtype
        tok_ids = jnp.arange(n, dtype=jnp.int32)
        slot_token = jnp.zeros((e * cap,), jnp.int32)
        slot_valid = jnp.zeros((e * cap,), dtype)
        for ei, pos_t, keep, _ in slots:
            flat = jnp.where(keep, ei * cap + pos_t, e * cap)  # OOB = drop
            slot_token = slot_token.at[flat].set(tok_ids, mode="drop")
            slot_valid = slot_valid.at[flat].set(
                jnp.ones((n,), dtype), mode="drop"
            )
        expert_in = (
            xf[slot_token] * slot_valid[:, None]
        ).reshape(e, cap, d)
        expert_out = self._experts(expert_in).reshape(e * cap, d)
        y = jnp.zeros((n, d), dtype)
        for ei, pos_t, keep, w in slots:
            flat = jnp.where(keep, ei * cap + pos_t, 0)
            wk = (w.astype(dtype) * keep.astype(dtype))[:, None]
            y = y + expert_out[flat] * wk
        return y.reshape(*lead, d)

    def _balance_loss(self, probs) -> jax.Array:
        me = jnp.mean(probs.reshape(-1, self.n_experts), axis=0)
        assign = jax.nn.one_hot(
            jnp.argmax(probs, axis=-1), self.n_experts, dtype=jnp.float32
        )
        ce = jnp.mean(assign.reshape(-1, self.n_experts), axis=0)
        return self.n_experts * jnp.sum(me * ce)

    def aux_load_balance_loss(self, x) -> jax.Array:
        """Switch-style load-balancing auxiliary loss.  Prefer
        ``forward(x, return_aux=True)``, which reuses the routing pass."""
        return self._balance_loss(self._route(x))


def moe_shard_rule(
    mesh, ep_axis: str = "ep", base_rule: Optional[Callable] = None
):
    """Sharding rule: expert-stacked weights shard their expert dim over
    ``ep_axis``; everything else falls through to ``base_rule`` (or
    replicates).  Compose with ``materialize_module`` or checkpoint
    restore."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def rule(path: str, like):
        leaf = path.rsplit(".", 1)[-1] if "." in path else path
        if leaf in ("w_gate", "w_up", "w_down") and like.ndim == 3:
            return NamedSharding(mesh, P(ep_axis, None, None))
        if base_rule is not None:
            return base_rule(path, like)
        return NamedSharding(mesh, P())

    return rule