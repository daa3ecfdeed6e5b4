"""The Jamba family's plain reference (``model_type: jamba``:
AI21-Jamba2-3B): Mamba-1 layers with an attention layer every
``attn_layer_period``, in straightforward ``jax.numpy`` and float32 at
``HIGHEST``.  No kernels, no cache, no state carried between calls: the
whole sequence from empty state, the recurrence a plain ``lax.scan``
over its tokens.

The equations (sizes from the configuration file; ``C`` =
``mamba_expand x hidden_size``, ``N`` = ``mamba_d_state``, ``R`` =
``mamba_dt_rank``, ``K`` = ``mamba_d_conv``)::

    x0 = E[tokens]                                         # no positional encoding anywhere
    layer l:  a = x + Mixer_l(RMSNorm(x; input_norm))
              x' = a + MLP(RMSNorm(a; pre_ff_norm)),  MLP(u) = W_down(silu(W_gate u) * W_up u)
    Mixer_l = Attention if l % attn_layer_period == attn_layer_offset else Mamba
    Attention(u): q = W_q u, k = W_k u, v = W_v u; causal softmax(q k^T / sqrt(head)) v; W_o.
                  No bias, no rope.
    Mamba(u):  [xs, z] = split(W_in u)                      # C each, no bias
               xc_t = silu(b_conv + sum_{j<K} w_conv[:, j] * xs_{t-(K-1)+j})   # depthwise, causal, xs_{<0} = 0
               [dr, B, C] = split(W_x xc_t)                 # R, N, N, no bias
               dr = RMSNorm(dr; dt_norm); B = RMSNorm(B; b_norm); C = RMSNorm(C; c_norm)
               D_t = softplus(W_dt dr + b_dt)
               A = -exp(A_log)                              # (C, N)
               h_t[c, n] = exp(D_t[c] * A[c, n]) * h_{t-1}[c, n] + D_t[c] * B_t[n] * xc_t[c],   h_{-1} = 0
               y_t[c] = sum_n C_t[n] * h_t[c, n] + Dskip[c] * xc_t[c]
               out = W_out(y_t * silu(z_t))
    logits = RMSNorm(x_L; norm) @ E^T                       # tied

It imports nothing of ``torchdistx_tpu`` and takes nothing the program
made.  ``leaf_plan`` names every parameter as the program's ``Jamba``
does, in construction order, so that the seed's rule arrives at the bits
``deferred_init`` -> ``materialize`` makes.  Every leaf follows that
rule: the drawn ones (``A_log``, the dt bias and the convolution among
them) are ``normal x initializer_range``, the norm scales and ``Dskip``
are ones (the configuration file's ``assumed`` says what that makes of
the step size).  A layer's weights are alive one layer at a time.  No
``TrainReference``: the family has no training cell.

**The planted fault** (``ServeReference(drop_state_at=)``): from the
given position of each row on, the Mamba layers go on from EMPTY state
(``h`` zeroed before that token, the convolution's window cut there) --
what a serving program does that loses a slot's recurrent state at the
seam between prefill and decode.  The attention layers are left whole.
``benchmarks/tests/test_jamba_family.py`` holds the check to it.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from harness.reference import HIGHEST, PRECISIONS, leaf, linear, rms_norm

__all__ = ["PRECISIONS", "Arch", "leaf_plan", "ServeReference", "FAULTS"]

#: the faults this reference can plant in itself, by name: what
#: ``ServeReference`` takes beside its usual arguments, from a sample's
#: ``(prompt length, total length)`` pairs
FAULTS = {
    # the recurrent state lost at the seam between prefill and decode
    "drop_state_at_seam": lambda lens: {
        "drop_state_at": [int(p) for p, _ in lens]},
}


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, under the published names."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    attn_layer_period: int
    attn_layer_offset: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_expand: int
    mamba_dt_rank: int
    rms_norm_eps: float
    dtype: str = "bfloat16"
    init_std: float = 0.02

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        ints = ("vocab_size", "hidden_size", "intermediate_size",
                "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "attn_layer_period",
                "attn_layer_offset", "mamba_d_state", "mamba_d_conv",
                "mamba_expand", "mamba_dt_rank")
        return cls(
            **{k: int(cfg[k]) for k in ints},
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            dtype=str(cfg.get("torch_dtype", "bfloat16")),
            init_std=float(cfg.get("initializer_range", 0.02)),
        )

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset


# -- the parameters, in construction order ------------------------------------


def block_leaves(a: Arch, layer: int):
    """One block's leaves in construction order: ``(name, shape, drawn)``;
    a leaf that is not drawn starts at one (the norm scales, ``D``).
    Matrices of a linear layer are (out, in)."""
    d, c, n, r = a.hidden_size, a.d_inner, a.mamba_d_state, a.mamba_dt_rank
    out = [("input_norm.weight", (d,), False)]
    if a.is_attention(layer):
        kv = a.num_key_value_heads * a.head_dim
        out += [("mixer.wq.weight", (d, d), True),
                ("mixer.wk.weight", (kv, d), True),
                ("mixer.wv.weight", (kv, d), True),
                ("mixer.wo.weight", (d, d), True)]
    else:
        out += [("mixer.in_proj.weight", (2 * c, d), True),
                ("mixer.conv_weight", (c, a.mamba_d_conv), True),
                ("mixer.conv_bias", (c,), True),
                ("mixer.x_proj.weight", (r + 2 * n, c), True),
                ("mixer.dt_norm.weight", (r,), False),
                ("mixer.b_norm.weight", (n,), False),
                ("mixer.c_norm.weight", (n,), False),
                ("mixer.dt_proj.weight", (c, r), True),
                ("mixer.dt_proj.bias", (c,), True),
                ("mixer.A_log", (c, n), True),
                ("mixer.D", (c,), False),
                ("mixer.out_proj.weight", (d, c), True)]
    f = a.intermediate_size
    out += [("pre_ff_norm.weight", (d,), False),
            ("mlp.w_gate.weight", (f, d), True),
            ("mlp.w_up.weight", (f, d), True),
            ("mlp.w_down.weight", (d, f), True)]
    return out


def leaf_plan(a: Arch):
    """Every parameter as ``(name, shape, counter)``; ``counter`` is None
    for a leaf that starts at one, else the leaf's number in the key
    stream.  No head: it is the embedding."""
    plan = [("tok_emb.weight", (a.vocab_size, a.hidden_size), 0)]
    c = 1
    for layer in range(a.num_hidden_layers):
        for name, shape, drawn in block_leaves(a, layer):
            plan.append((f"blocks.{layer}.{name}", shape, c if drawn else None))
            c += int(drawn)
    plan.append(("norm.weight", (a.hidden_size,), None))
    return plan


def block_weights_from_seed(a: Arch, seed: int, layer: int, plan=None) -> dict:
    """The leaves of block ``layer`` by their names within the block."""
    pre = f"blocks.{layer}."
    return {name[len(pre):]: leaf(a, seed, counter, shape)
            for name, shape, counter in (plan or leaf_plan(a))
            if name.startswith(pre)}


# -- the mathematics --------------------------------------------------------


def attention(a: Arch, precision: str, u, w):
    """Causal softmax attention, float32, the KV heads shared by their
    groups of query heads.  A head of a row at a time: the scores are
    T x T."""
    b, t, _ = u.shape
    h, kv, hd = a.num_attention_heads, a.num_key_value_heads, a.head_dim
    q = linear(u, w["mixer.wq.weight"], precision).reshape(b, t, h, hd)
    k = linear(u, w["mixer.wk.weight"], precision).reshape(b, t, kv, hd)
    v = linear(u, w["mixer.wv.weight"], precision).reshape(b, t, kv, hd)
    k, v = (jnp.repeat(x, h // kv, axis=2) for x in (k, v))
    mask = jnp.tril(jnp.ones((t, t), bool))
    scale = 1.0 / math.sqrt(hd)

    def one_head(qkv):
        q1, k1, v1 = qkv  # (T, hd) each
        s = jnp.einsum("td,sd->ts", q1, k1, precision=HIGHEST) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("ts,sd->td", p, v1, precision=HIGHEST)

    def one_row(qkv):
        heads_first = [jnp.swapaxes(x, 0, 1) for x in qkv]  # (H, T, hd)
        return jnp.swapaxes(jax.lax.map(one_head, heads_first), 0, 1)

    o = jax.lax.map(one_row, (q, k, v)).reshape(b, t, h * hd)
    return linear(o, w["mixer.wo.weight"], precision)


def mamba(a: Arch, precision: str, u, w, drop_at):
    """The Mamba mixer on the normed ``u`` (B, T, D).  ``drop_at`` (B,)
    int32: the planted fault's position a row (the state is dropped
    before that token), or a position the row never reaches."""
    b, t, _ = u.shape
    c, n, r, k = a.d_inner, a.mamba_d_state, a.mamba_dt_rank, a.mamba_d_conv
    f32 = lambda name: w[name].astype(jnp.float32)  # noqa: E731
    xz = linear(u, w["mixer.in_proj.weight"], precision)
    xs, z = xz[..., :c], xz[..., c:]
    # the causal depthwise convolution: output t reads inputs t-(K-1) .. t
    pos = jnp.arange(t)
    after = pos[None, :] >= drop_at[:, None]  # (B, T): output at or past the fault
    ext = jnp.concatenate([jnp.zeros((b, k - 1, c), jnp.float32), xs], axis=1)
    acc = f32("mixer.conv_bias")
    for j in range(k):
        src = pos - (k - 1) + j  # the input position this tap reads
        lost = after & (src[None, :] < drop_at[:, None])
        tap = jnp.where(lost[..., None], 0.0, ext[:, j:j + t])
        acc = acc + f32("mixer.conv_weight")[:, j] * tap
    xc = jax.nn.silu(acc)
    dbc = linear(xc, w["mixer.x_proj.weight"], precision)
    eps = a.rms_norm_eps
    dr = rms_norm(dbc[..., :r], w["mixer.dt_norm.weight"], eps)
    bm = rms_norm(dbc[..., r:r + n], w["mixer.b_norm.weight"], eps)
    cm = rms_norm(dbc[..., r + n:], w["mixer.c_norm.weight"], eps)
    dt = jax.nn.softplus(
        linear(dr, w["mixer.dt_proj.weight"], precision) + f32("mixer.dt_proj.bias"))
    neg_a = -jnp.exp(f32("mixer.A_log"))  # (C, N)

    def step(h, row):
        i, dt_t, x_t, b_t, c_t = row  # (B, C), (B, C), (B, N), (B, N)
        h = jnp.where((i == drop_at)[:, None, None], 0.0, h)
        h = (jnp.exp(dt_t[..., None] * neg_a) * h
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    rows = (pos,) + tuple(jnp.moveaxis(v, 1, 0) for v in (dt, xc, bm, cm))
    _, y = jax.lax.scan(step, jnp.zeros((b, c, n), jnp.float32), rows)
    y = jnp.moveaxis(y, 0, 1) + f32("mixer.D") * xc
    return linear(y * jax.nn.silu(z), w["mixer.out_proj.weight"], precision)


def block(a: Arch, precision: str, is_attention: bool, x, w, drop_at):
    """One decoder block.  x (B, T, D) float32; ``w`` maps a block's leaf
    names (without the ``blocks.N.`` prefix) to arrays."""
    u = rms_norm(x, w["input_norm.weight"], a.rms_norm_eps)
    if is_attention:
        x = x + attention(a, precision, u, w)
    else:
        x = x + mamba(a, precision, u, w, drop_at)
    u = rms_norm(x, w["pre_ff_norm.weight"], a.rms_norm_eps)
    gated = (jax.nn.silu(linear(u, w["mlp.w_gate.weight"], precision))
             * linear(u, w["mlp.w_up.weight"], precision))
    return x + linear(gated, w["mlp.w_down.weight"], precision)


def head_logits(a: Arch, precision: str, x, norm_w, emb):
    return linear(rms_norm(x, norm_w, a.rms_norm_eps), emb, precision)


# -- serving: logits of whole sequences, weights never all alive ------------


class ServeReference:
    """Logits of whole (N, T) sequences, float32, a layer's weights alive
    at a time; ``logits_rows`` hands them out a row at a time, since
    (N, T, vocab) in one piece is too much.  ``drop_state_at`` (a
    position a row, or None) plants the fault of the module docstring."""

    def __init__(self, arch: Arch, seed: int, precision: str = "f32",
                 drop_state_at=None):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.a, self.seed, self.precision = arch, seed, precision
        self.drop_state_at = drop_state_at
        a = arch
        self.plan = leaf_plan(a)
        self._embed = jax.jit(
            lambda emb, tokens: jnp.take(emb, tokens, axis=0).astype(jnp.float32))
        self._attention = jax.jit(
            lambda x, w, at: block(a, precision, True, x, w, at))
        self._mamba = jax.jit(
            lambda x, w, at: block(a, precision, False, x, w, at))
        self._head = jax.jit(
            lambda x, norm_w, emb: head_logits(a, precision, x, norm_w, emb))

    def _embedding(self):
        a = self.a
        return leaf(a, self.seed, 0, (a.vocab_size, a.hidden_size))

    def hidden(self, tokens):
        a = self.a
        tokens = jnp.asarray(tokens, jnp.int32)
        never = tokens.shape[1]  # a position no row reaches
        drop_at = jnp.full((tokens.shape[0],), never, jnp.int32) if (
            self.drop_state_at is None) else jnp.asarray(
                self.drop_state_at, jnp.int32)
        emb = self._embedding()
        x = self._embed(emb, tokens)
        del emb
        for layer in range(a.num_hidden_layers):
            w = block_weights_from_seed(a, self.seed, layer, self.plan)
            step = self._attention if a.is_attention(layer) else self._mamba
            x = step(x, w, drop_at)
            del w
        return x

    def logits_rows(self, tokens):
        """Yield (row index, (T, vocab) float32 device array)."""
        a = self.a
        x = self.hidden(tokens)
        emb = self._embedding()  # tied: the head is the embedding
        norm_w = jnp.ones((a.hidden_size,), a.jdtype)
        for i in range(x.shape[0]):
            yield i, self._head(x[i:i + 1], norm_w, emb)[0]
