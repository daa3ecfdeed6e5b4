"""The Llama family's plain reference: a Llama-architecture decoder (RMSNorm,
rotary MHA/GQA, SwiGLU) in straightforward ``jax.numpy`` and float32, with
its loss, its gradients (layer by layer) and the AdamW update the
configuration states.

The architecture's mathematics only.  It imports nothing of
``torchdistx_tpu`` and takes nothing the program made; the seed's rule,
the controls' ``linear`` and the comparisons are the harness's
(``harness/reference.py``), shared by every family.  ``leaf_plan`` names
every parameter as the program's Llama does, in construction order, so
that the seed's rule arrives at the bits ``deferred_init`` ->
``materialize`` makes.

Everything is computed per layer, with the layer's weights made on the
spot, so that a 7B reference fits beside nothing else on one chip.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from harness.reference import (HIGHEST, PRECISIONS, AdamW, adamw_leaf,
                               change_norm_against_seed, leaf, linear, rms_norm)

__all__ = ["PRECISIONS", "Arch", "leaf_plan", "ServeReference",
           "TrainReference", "sample_leaves"]


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


# -- the parameters, in construction order ------------------------------------


def block_matrices(a: Arch):
    """One block's matrices in construction order: (name, rows, columns)."""
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


# -- the mathematics --------------------------------------------------------


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


# -- training: loss and gradients layer by layer ----------------------------


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
        return change_norm_against_seed(self.a, leaf_plan(self.a), self.seed,
                                        self.params)


def sample_leaves(arch: Arch) -> tuple:
    """The leaves whose first gradient is compared element by element:
    the first block's query matrix (the longest way back), a middle
    block's output projection and the last block's down projection."""
    last = arch.num_hidden_layers - 1
    return ("blocks.0.attn.wq.weight",
            f"blocks.{last // 2}.attn.wo.weight",
            f"blocks.{last}.mlp.w_down.weight")
