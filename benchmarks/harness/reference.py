"""What every family's plain reference is built from, and holds no
architecture: the rule that makes a weight from the seed, the controls'
``linear`` (float32, bfloat16 or int8 operands), ``rms_norm``, the stated
AdamW, and the comparisons of a program's weights, parameter change and
served tokens with a reference's.

The mathematics of an architecture (its sizes, its ``leaf_plan``, its
forward, loss and gradients) lives beside its family, under
``families/``, and reaches the drivers through the family alone
(``ctx.family().reference``).  What here needs the sizes takes the
family's ``arch`` (for its ``jdtype`` and ``init_std``) and the family's
``plan`` (``leaf_plan(arch)``: every parameter as ``(name, shape,
counter)``) as arguments.

Nothing here imports ``torchdistx_tpu`` or takes anything the program
made.  A weight comes from the seed by the rule the configuration files
state (``weights``): parameter number ``c`` in construction order is
``normal(fold_in(PRNGKey(seed), c), shape, dtype) * init_std``, norm
scales are ones.  The program's ``deferred_init`` -> ``materialize`` has
to arrive at the same bits, or every comparison reads far off.

``precision`` is ``"f32"`` (float32 operands, ``HIGHEST`` matmul
precision: the reference), or a control: ``"int8"`` (every linear
layer's matmuls, forward and backward, with both operands rounded to 8
bits, one scale per slice along the contracted axis, accumulation still
float32 -- the nearest precision below the bfloat16 the configurations
state, and the one a v5e's int8 MXU would tempt) or ``"bf16"`` (operands
rounded to bfloat16: the control of a float32 configuration, which only
the rehearsal files have).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .traffic import seed31

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("f32", "bf16", "int8")


# -- weights from the seed --------------------------------------------------


def leaf(a, seed: int, counter, shape):
    """One parameter from the stream, bit for bit what the rule says.

    Two separate dispatches on purpose: the draw, then the scaling.  Fused
    into one program (or into a larger one) the compiler may keep the
    draw in float32 and round once, which moves some elements by an ulp;
    the program's materialization replays its recorded operations one by
    one, and the rule is written after that."""
    if counter is None:
        return jnp.ones(shape, a.jdtype)
    key = jax.random.fold_in(jax.random.PRNGKey(seed31(seed)), counter)
    draw = jax.random.normal(key, tuple(shape), a.jdtype)
    return draw * jnp.asarray(a.init_std, a.jdtype)


# -- the controls' linear layer, and the norm ----------------------------------


def _round8(x, axis):
    """Round to 8 bits with one scale per slice along ``axis`` (the axis
    the matmul contracts)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@jax.custom_vjp
def linear_int8(x, w):
    """``x @ w.T`` as an int8 step would make it: both operands of the
    forward matmul and of the two backward matmuls rounded to 8 bits
    along the contracted axis, accumulation in float32."""
    return jnp.einsum("...k,nk->...n", _round8(x, -1), _round8(w, -1),
                      precision=HIGHEST)


def _linear_int8_fwd(x, w):
    return linear_int8(x, w), (x, w)


def _linear_int8_bwd(res, dy):
    x, w = res
    x2, dy2 = x.reshape(-1, x.shape[-1]), dy.reshape(-1, dy.shape[-1])
    dx = jnp.einsum("tn,nk->tk", _round8(dy2, -1), _round8(w, 0),
                    precision=HIGHEST)
    dw = jnp.einsum("tn,tk->nk", _round8(dy2, 0), _round8(x2, 0),
                    precision=HIGHEST)
    return dx.reshape(x.shape), dw


linear_int8.defvjp(_linear_int8_fwd, _linear_int8_bwd)


def linear(x, w, precision):
    """``x @ w.T``: x (..., K) float32, w (N, K) any float dtype."""
    w = w.astype(jnp.float32)
    if precision == "int8":
        return linear_int8(x, w)
    if precision == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.einsum("...k,nk->...n", x, w, precision=HIGHEST)


def rms_norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


# -- serving: served tokens against a reference's logits -----------------------


@jax.jit
def _gaps_of(logits, tokens):
    """For every position: how far the given token's logit lies under the
    row's best, and which token is best."""
    best = jnp.max(logits, axis=-1)
    mine = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best - mine, jnp.argmax(logits, axis=-1)


def served_gaps(ref, sequences, prompt_lens, control=None):
    """``ref`` and ``control`` are a family's serve references: anything
    whose ``logits_rows(sequences)`` yields ``(row, (T, vocab) float32
    logits)`` in row order.
    ``sequences`` (N, T) int32 hold prompt + served tokens, padded;
    ``prompt_lens`` their (prompt, prompt + served) lengths.  Per request:
    the widest gap by which a served token's reference logit lies under
    the reference's best, and the sum of those gaps; with ``control`` the
    same for the tokens the control's logits put first.  Returns
    ``{"max": [...], "sum": [...], "tokens": [...]}`` for the served
    tokens, and the same (or None) for the control's."""
    sequences = np.asarray(sequences, np.int32)
    t = sequences.shape[1]
    out = {"max": [], "sum": [], "tokens": []}
    out_control = {"max": [], "sum": [], "tokens": []} if control else None
    control_rows = control.logits_rows(sequences) if control else None
    for i, logits in ref.logits_rows(sequences):
        p, total = int(prompt_lens[i][0]), int(prompt_lens[i][1])
        # position j predicts token j + 1: served tokens sit at p .. total-1
        nxt = np.zeros((t,), np.int32)
        nxt[:-1] = sequences[i, 1:]
        gap, _ = _gaps_of(logits, jnp.asarray(nxt))
        pairs = [(out, gap)]
        if control_rows is not None:
            _, clogits = next(control_rows)
            first = jnp.argmax(clogits, axis=-1).astype(jnp.int32)
            pairs.append((out_control, _gaps_of(logits, first)[0]))
        for dest, g in pairs:
            g = g[p - 1:total - 1]
            dest["max"].append(float(jnp.max(g)))
            dest["sum"].append(float(jnp.sum(g)))
            dest["tokens"].append(total - p)
    return out, out_control


# -- training: the stated AdamW, and a program's leaves against the seed's ----


@dataclasses.dataclass(frozen=True)
class AdamW:
    """AnyPrecisionAdamW as the cell states it: float32 first moment,
    bfloat16 second moment, no Kahan buffer, the update rounded to the
    parameters' type before it is added."""

    lr: float = 1e-4
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    variance_dtype: str = "bfloat16"


def adamw_leaf(opt: AdamW, step, p, g, m, v):
    g = g.astype(jnp.float32)
    m = m * opt.b1 + g * (1.0 - opt.b1)
    v = (v.astype(jnp.float32) * opt.b2 + g * g * (1.0 - opt.b2)).astype(
        v.dtype)
    bc1 = 1.0 - opt.b1 ** step
    bc2 = 1.0 - opt.b2 ** step
    denom = jnp.sqrt(v.astype(jnp.float32)) / jnp.sqrt(bc2) + opt.eps
    delta = -(opt.lr / bc1) * (m / denom)
    if opt.weight_decay:
        delta = delta - opt.lr * opt.weight_decay * p.astype(jnp.float32)
    return p + delta.astype(p.dtype), m, v


@jax.jit
def _diff_norm(a, b):
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(d * d))


@jax.jit
def _differs(a, b):
    return jnp.any(a != b)


def change_norm_against_seed(arch, plan, seed: int, params: dict) -> dict:
    """Per leaf of ``params`` (the program's or the reference's, by the
    plan's names): the norm of its distance from what the seed started it
    at.  A leaf's starting value is alive for its own comparison only."""
    return {name: _diff_norm(params[name], leaf(arch, seed, counter, shape))
            for name, shape, counter in plan}


def weights_differ(arch, plan, seed: int, params: dict) -> int:
    """How many leaves of ``params`` are not, bit for bit, what the rule
    makes from the seed (a leaf of another shape or type counts, and so
    does a leaf the plan does not know)."""
    flags, wrong = [], 0
    for name, shape, counter in plan:
        p = params.get(name)
        if p is None or tuple(p.shape) != tuple(shape) or p.dtype != arch.jdtype:
            wrong += 1
            continue
        flags.append(_differs(p, leaf(arch, seed, counter, shape)))
    return wrong + int(sum(bool(f) for f in flags)) + max(
        0, len(params) - len(plan))


@jax.jit
def diff_rel(a, b):
    """``|a - b| / |b|``, norms over the whole leaf."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(a - b)) / jnp.sum(jnp.square(b)))


@jax.jit
def tree_norms(tree):
    """The norm of every leaf, in one program."""
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)
