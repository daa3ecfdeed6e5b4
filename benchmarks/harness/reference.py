"""The plain reference: a Llama-architecture decoder in straightforward
``jax.numpy`` and float32, with its loss, its gradients (layer by layer)
and the AdamW update the configuration states.

It imports nothing of ``torchdistx_tpu`` and takes nothing the program
made.  Its weights come from the seed by the rule the configuration
files state (``weights``): parameter number ``c`` in construction order
is ``normal(fold_in(PRNGKey(seed), c), shape, dtype) * 0.02``, norm
scales are ones.  The program's ``deferred_init`` -> ``materialize``
has to arrive at the same bits, or every comparison below reads far off.

Everything is computed per layer, with the layer's weights made on the
spot, so that a 7B reference fits beside nothing else on one chip.

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
import math

import jax
import jax.numpy as jnp
import numpy as np

from .traffic import seed31

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("f32", "bf16", "int8")


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, under the published names."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float
    rms_norm_eps: float
    max_position_embeddings: int
    dtype: str = "bfloat16"
    init_std: float = 0.02

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        return cls(
            vocab_size=int(cfg["vocab_size"]),
            hidden_size=int(cfg["hidden_size"]),
            num_hidden_layers=int(cfg["num_hidden_layers"]),
            num_attention_heads=int(cfg["num_attention_heads"]),
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            intermediate_size=int(cfg["intermediate_size"]),
            rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            max_position_embeddings=int(cfg["max_position_embeddings"]),
            dtype=str(cfg.get("torch_dtype", "bfloat16")),
            init_std=float(cfg.get("initializer_range", 0.02)),
        )

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)


# -- weights from the seed --------------------------------------------------

#: one block's matrices in construction order: (name, rows, columns)
def block_matrices(a: Arch):
    q = a.num_attention_heads * a.head_dim
    kv = a.num_key_value_heads * a.head_dim
    d, f = a.hidden_size, a.intermediate_size
    return (
        ("attn.wq", q, d), ("attn.wk", kv, d), ("attn.wv", kv, d),
        ("attn.wo", d, q), ("mlp.w_gate", f, d), ("mlp.w_up", f, d),
        ("mlp.w_down", d, f),
    )


def leaf_plan(a: Arch):
    """Every parameter as ``(name, shape, counter)``; ``counter`` is None
    for a norm scale (ones), else the leaf's number in the key stream."""
    plan = [("tok_emb.weight", (a.vocab_size, a.hidden_size), 0)]
    c = 1
    for layer in range(a.num_hidden_layers):
        pre = f"blocks.{layer}."
        plan.append((pre + "attn_norm.weight", (a.hidden_size,), None))
        mats = block_matrices(a)
        for name, rows, cols in mats[:4]:
            plan.append((pre + name + ".weight", (rows, cols), c))
            c += 1
        plan.append((pre + "mlp_norm.weight", (a.hidden_size,), None))
        for name, rows, cols in mats[4:]:
            plan.append((pre + name + ".weight", (rows, cols), c))
            c += 1
    plan.append(("norm.weight", (a.hidden_size,), None))
    plan.append(("lm_head.weight", (a.vocab_size, a.hidden_size), c))
    return plan


def leaf(a: Arch, seed: int, counter, shape):
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


# -- the mathematics --------------------------------------------------------


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


def rope_tables(a: Arch, length: int):
    inv = 1.0 / (a.rope_theta ** (
        jnp.arange(0, a.head_dim, 2, dtype=jnp.float32) / a.head_dim))
    ang = jnp.outer(jnp.arange(length, dtype=jnp.float32), inv)
    return jnp.cos(ang), jnp.sin(ang)  # (T, hd/2) each


def rope(x, cos, sin):
    """x (B, T, H, hd): the half-split rotation (first half with second)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v):
    """Causal softmax attention, grouped queries: q (B,T,H,hd), k/v
    (B,T,Hkv,hd), float32 throughout."""
    b, t, h, hd = q.shape
    g = h // k.shape[2]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def one_row(qkv):  # a row of the batch at a time: the scores are T x T
        q1, k1, v1 = qkv
        q1 = q1.reshape(t, k1.shape[1], g, hd)
        s = jnp.einsum("tkgd,skd->kgts", q1, k1, precision=HIGHEST)
        s = jnp.where(mask[None, None], s / math.sqrt(hd), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgts,skd->tkgd", p, v1, precision=HIGHEST)
        return o.reshape(t, h * hd)

    return jax.lax.map(one_row, (q, k, v))


def block(a: Arch, precision: str, x, w):
    """One decoder block.  x (B,T,D) float32; ``w`` maps the nine leaf
    names of a block (without the ``blocks.N.`` prefix) to arrays."""
    b, t, _ = x.shape
    cos, sin = rope_tables(a, t)
    h = rms_norm(x, w["attn_norm"], a.rms_norm_eps)
    q = linear(h, w["attn.wq"], precision).reshape(
        b, t, a.num_attention_heads, a.head_dim)
    k = linear(h, w["attn.wk"], precision).reshape(
        b, t, a.num_key_value_heads, a.head_dim)
    v = linear(h, w["attn.wv"], precision).reshape(
        b, t, a.num_key_value_heads, a.head_dim)
    o = attention(rope(q, cos, sin), rope(k, cos, sin), v)
    x = x + linear(o, w["attn.wo"], precision)
    h = rms_norm(x, w["mlp_norm"], a.rms_norm_eps)
    gate = jax.nn.silu(linear(h, w["mlp.w_gate"], precision))
    up = linear(h, w["mlp.w_up"], precision)
    return x + linear(gate * up, w["mlp.w_down"], precision)


def head_logits(a: Arch, precision: str, x, norm_w, head_w):
    return linear(rms_norm(x, norm_w, a.rms_norm_eps), head_w, precision)


def head_loss(a: Arch, precision: str, x, norm_w, head_w, labels):
    """Mean token cross-entropy of the last hidden states."""
    logits = head_logits(a, precision, x, norm_w, head_w)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


BLOCK_LEAVES = ("attn_norm", "attn.wq", "attn.wk", "attn.wv", "attn.wo",
                "mlp_norm", "mlp.w_gate", "mlp.w_up", "mlp.w_down")


def block_weights_from_seed(a: Arch, seed: int, layer: int) -> dict:
    """The nine leaves of block ``layer``."""
    first = 1 + 7 * layer
    out = {"attn_norm": jnp.ones((a.hidden_size,), a.jdtype),
           "mlp_norm": jnp.ones((a.hidden_size,), a.jdtype)}
    for i, (name, rows, cols) in enumerate(block_matrices(a)):
        out[name] = leaf(a, seed, first + i, (rows, cols))
    return out


# -- serving: logits of whole sequences, weights never all alive ------------


class ServeReference:
    """Logits of whole (N, T) sequences, float32, a layer's weights alive
    at a time; ``logits_rows`` hands them out a row at a time, since
    (N, T, vocab) in one piece is too much."""

    def __init__(self, arch: Arch, seed: int, precision: str = "f32"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.a, self.seed, self.precision = arch, seed, precision
        a = arch
        self._embed = jax.jit(
            lambda emb, tokens: jnp.take(emb, tokens, axis=0).astype(jnp.float32))
        self._block = jax.jit(lambda x, w: block(a, precision, x, w))
        self._head = jax.jit(
            lambda x, norm_w, head_w: head_logits(a, precision, x, norm_w, head_w))

    def hidden(self, tokens):
        a = self.a
        emb = leaf(a, self.seed, 0, (a.vocab_size, a.hidden_size))
        x = self._embed(emb, jnp.asarray(tokens, jnp.int32))
        del emb
        for layer in range(a.num_hidden_layers):
            x = self._block(x, block_weights_from_seed(a, self.seed, layer))
        return x

    def logits_rows(self, tokens):
        """Yield (row index, (T, vocab) float32 device array)."""
        a = self.a
        x = self.hidden(tokens)
        head_w = leaf(a, self.seed, 1 + 7 * a.num_hidden_layers,
                      (a.vocab_size, a.hidden_size))
        norm_w = jnp.ones((a.hidden_size,), a.jdtype)
        for i in range(x.shape[0]):
            yield i, self._head(x[i:i + 1], norm_w, head_w)[0]


@jax.jit
def _gaps_of(logits, tokens):
    """For every position: how far the given token's logit lies under the
    row's best, and which token is best."""
    best = jnp.max(logits, axis=-1)
    mine = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best - mine, jnp.argmax(logits, axis=-1)


def served_gaps(ref: ServeReference, sequences, prompt_lens,
                control: ServeReference | None = None):
    """``sequences`` (N, T) int32 hold prompt + served tokens, padded;
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


# -- training: loss, gradients layer by layer, the stated AdamW -------------


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


class TrainReference:
    """Three things per step: the loss, every leaf's gradient norm, and
    the stated update.  The backward pass is written out layer by layer
    (each block's inputs are kept, its forward is run again under
    ``jax.vjp``), and a leaf is updated the moment its gradient exists,
    so that no more than one block's gradients are ever alive."""

    def __init__(self, arch: Arch, seed: int, opt: AdamW,
                 precision: str = "f32", rows: slice | None = None):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.a, self.seed, self.opt, self.precision = arch, seed, opt, precision
        #: the fault "half of the batch left out": rows to keep
        self.rows = rows
        #: leaves whose first gradient is kept whole (``sample_leaves``)
        self.keep, self.kept = (), {}
        a = arch
        self.params, self.m, self.v = {}, {}, {}
        for name, shape, counter in leaf_plan(a):
            self.params[name] = leaf(a, seed, counter, shape)
        for name, p in self.params.items():
            self.m[name] = jnp.zeros(p.shape, jnp.float32)
            self.v[name] = jnp.zeros(p.shape, jnp.dtype(opt.variance_dtype))
        self.steps = 0

        @jax.jit
        def embed(emb, tokens):
            return jnp.take(emb, tokens, axis=0).astype(jnp.float32)

        @jax.jit
        def fwd(x, w):
            return block(a, precision, x, w)

        def up(tree):  # gradients in float32: upcast outside the vjp
            return jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), tree)

        @jax.jit
        def bwd(x, w, dy):
            _, vjp = jax.vjp(lambda x_, w_: block(a, precision, x_, w_), x, up(w))
            return vjp(dy)

        @jax.jit
        def head(x, norm_w, head_w, labels):
            return jax.value_and_grad(
                lambda x_, n_, h_: head_loss(a, precision, x_, n_, h_, labels),
                argnums=(0, 1, 2))(x, up(norm_w), up(head_w))

        @jax.jit
        def emb_grad(dx, tokens):
            flat = dx.reshape(-1, dx.shape[-1])
            return jnp.zeros((a.vocab_size, a.hidden_size), jnp.float32).at[
                tokens.reshape(-1)].add(flat)

        @jax.jit
        def apply(step, p, g, m, v):
            gn = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
            return (*adamw_leaf(opt, step, p, g, m, v), gn)

        self._embed, self._fwd, self._bwd = embed, fwd, bwd
        self._head, self._emb_grad, self._apply = head, emb_grad, apply

    def _block_w(self, layer):
        pre = f"blocks.{layer}."
        return {k: self.params[pre + k + ".weight"] for k in BLOCK_LEAVES}

    def _update(self, name, g, norms):
        if name in self.keep and self.steps == 0:
            self.kept[name] = g
        step = jnp.float32(self.steps + 1)
        p, m, v, gn = self._apply(step, self.params[name], g, self.m[name],
                                  self.v[name])
        self.params[name], self.m[name], self.v[name] = p, m, v
        norms[name] = gn

    def step(self, tokens, labels):
        """One step on a (B, T) batch.  Returns (loss, {leaf: grad norm})
        as device scalars."""
        tokens = jnp.asarray(tokens, jnp.int32)
        labels = jnp.asarray(labels, jnp.int32)
        if self.rows is not None:
            tokens, labels = tokens[self.rows], labels[self.rows]
        a = self.a
        xs = [self._embed(self.params["tok_emb.weight"], tokens)]
        for layer in range(a.num_hidden_layers):
            xs.append(self._fwd(xs[-1], self._block_w(layer)))
        loss, (dx, dnorm, dhead) = self._head(
            xs.pop(), self.params["norm.weight"],
            self.params["lm_head.weight"], labels)
        norms = {}
        self._update("norm.weight", dnorm, norms)
        self._update("lm_head.weight", dhead, norms)
        del dnorm, dhead
        for layer in reversed(range(a.num_hidden_layers)):
            dx, dw = self._bwd(xs.pop(), self._block_w(layer), dx)
            for k in BLOCK_LEAVES:
                self._update(f"blocks.{layer}.{k}.weight", dw[k], norms)
            del dw
        self._update("tok_emb.weight", self._emb_grad(dx, tokens), norms)
        self.steps += 1
        return loss, norms

    def change_norms(self):
        """Per leaf, the norm of (parameter now - parameter at the start)."""
        return change_norm_against_seed(self.a, self.seed, self.params)


@jax.jit
def _diff_norm(a, b):
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(d * d))


@jax.jit
def _differs(a, b):
    return jnp.any(a != b)


def change_norm_against_seed(arch: Arch, seed: int, params: dict) -> dict:
    """Per leaf of ``params`` (the program's or the reference's, by the
    plan's names): the norm of its distance from what the seed started it
    at.  A leaf's starting value is alive for its own comparison only."""
    return {name: _diff_norm(params[name], leaf(arch, seed, counter, shape))
            for name, shape, counter in leaf_plan(arch)}


def weights_differ(arch: Arch, seed: int, params: dict) -> int:
    """How many leaves of ``params`` are not, bit for bit, what the rule
    makes from the seed (a leaf of another shape or type counts)."""
    flags, wrong = [], 0
    for name, shape, counter in leaf_plan(arch):
        p = params.get(name)
        if p is None or tuple(p.shape) != tuple(shape) or p.dtype != arch.jdtype:
            wrong += 1
            continue
        flags.append(_differs(p, leaf(arch, seed, counter, shape)))
    return wrong + int(sum(bool(f) for f in flags)) + max(
        0, len(params) - len(leaf_plan(arch)))


def sample_leaves(arch: Arch) -> tuple:
    """The leaves whose first gradient is compared element by element:
    the first block's query matrix (the longest way back), a middle
    block's output projection and the last block's down projection."""
    last = arch.num_hidden_layers - 1
    return ("blocks.0.attn.wq.weight",
            f"blocks.{last // 2}.attn.wo.weight",
            f"blocks.{last}.mlp.w_down.weight")


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
