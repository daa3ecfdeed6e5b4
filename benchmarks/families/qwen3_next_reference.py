"""The Qwen3-Next family's plain reference (``model_type: qwen3_next``:
Qwen3-Next-80B-A3B): Gated-DeltaNet layers with a gated softmax
attention layer every ``full_attention_interval``, every feed-forward an
expert layer with a gated shared expert, in straightforward
``jax.numpy`` and float32 at ``HIGHEST``.  No kernels, no cache, no
chunked form: the whole sequence from empty state, the delta rule a
plain ``lax.scan`` over its tokens, every expert layer a masked sum over
the experts it holds.

The equations (sizes from the configuration file; ``Hk`` / ``Hv`` =
``linear_num_key_heads`` / ``linear_num_value_heads``, ``Dk`` / ``Dv`` =
``linear_key_head_dim`` / ``linear_value_head_dim``, ``K`` =
``linear_conv_kernel_dim``, ``D`` = ``head_dim``, ``R`` =
``partial_rotary_factor x D``)::

    x0 = E[tokens]
    layer l:  a  = x + Mixer_l(RMSNorm(x; input_norm))
              x' = a + MoE(RMSNorm(a; post_norm))
    Mixer_l = Attention if (l + 1) % full_attention_interval == 0 else GatedDeltaNet
    RMSNorm(u; s) = u / sqrt(mean(u^2) + eps) * s        # s = 1 + w published: the program stores s (ones)

    GatedDeltaNet(u):
      [q, k, v, z] = split(W_qkvz u)            # Hk Dk, Hk Dk, Hv Dv, Hv Dv; no bias
      [b, a]       = split(W_ba u)              # Hv, Hv
      [q, k, v]_t  = silu(sum_{j<K} w_conv[:, j] * [q, k, v]_{t-(K-1)+j})   # depthwise causal, no bias
      per value head h (key head h // (Hv / Hk)):  q = l2norm(q_h) / sqrt(Dk),  k = l2norm(k_h)   # eps 1e-6
      beta_t = sigmoid(b_t[h]);   g_t = -exp(A_log[h]) * softplus(a_t[h] + dt_bias[h])
      S' = exp(g_t) * S_{t-1}                   # S: (Dk, Dv), S_{-1} = 0
      d  = beta_t * (v_t - S'^T k_t)
      S_t = S' + k_t d^T
      o_t = S_t^T q_t
      y = RMSNorm(o_t; w_norm over Dv) * silu(z_t[h])
      out = W_out concat_h(y)

    Attention(u):
      [qg] = W_q u -> per head (query D, gate D);  k = W_k u, v = W_v u;  no bias
      q = rope_R(RMSNorm(query; q_norm)),  k = rope_R(RMSNorm(k; k_norm))   # rotate-half pairs (i, i + R/2) of the first R lanes
      o = causal softmax(q k^T / sqrt(D)) v
      out = W_o (o * sigmoid(gate))

    MoE(u):  p = softmax(W_r u) over router_width;  top num_experts_per_tok;  w = p_top / sum(p_top)
             y = sum_{i: lo <= e_i < hi} w_i * E_{e_i}(u) + sigmoid(w_sg . u) * E_shared(u)
             E(u) = W_down(silu(W_gate u) * W_up u)
    logits = RMSNorm(x_L; norm) @ W_head^T

**The share.**  The configuration holds experts ``experts_held = [lo,
hi)`` of ``router_width``: the router is ``router_width`` wide, the
choice and the renormalisation run over all of them, and ``y`` sums the
chosen experts that are held here (``num_experts`` of them) -- one
chip's partial result, which goes on to the next layer as it does in the
program.  No multi-token-prediction module (the configuration has no key
of one).  The column order inside ``W_qkvz`` / ``W_ba`` is contiguous
(``q | k | v | z``, ``b | a``), as the program's.

It imports nothing of ``torchdistx_tpu`` and takes nothing the program
made.  ``leaf_plan`` names every parameter as the program's
``Qwen3Next`` does, in construction order, so that the seed's rule
arrives at the bits ``deferred_init`` -> ``materialize`` makes.  Every
leaf follows that rule: the drawn ones (``A_log``, ``dt_bias`` and the
convolution among them) are ``normal x initializer_range``, the norm
scales are ones.  A layer's weights are alive one layer at a time.  No
``TrainReference``: the family has no training cell.

**The planted fault** (``ServeReference(drop_state_at=)``): from the
given position of each row on, the Gated-DeltaNet layers go on from
EMPTY state (``S`` zeroed before that token, the convolution's window
cut there) -- what a serving program does that loses a slot's recurrent
state at the seam between prefill and decode.  The attention layers are
left whole.
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
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    full_attention_interval: int
    linear_conv_kernel_dim: int
    linear_key_head_dim: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_value_head_dim: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts: int  # held here
    num_experts_per_tok: int
    router_width: int
    held_from: int
    partial_rotary_factor: float
    rope_theta: float
    rms_norm_eps: float
    dtype: str = "bfloat16"
    init_std: float = 0.02

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        ints = ("vocab_size", "hidden_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "full_attention_interval", "linear_conv_kernel_dim",
                "linear_key_head_dim", "linear_num_key_heads",
                "linear_num_value_heads", "linear_value_head_dim",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "num_experts", "num_experts_per_tok")
        lo, hi = cfg.get("experts_held", (0, cfg["num_experts"]))
        if hi - lo != cfg["num_experts"]:
            raise ValueError(
                f"experts_held {[lo, hi]} does not hold num_experts="
                f"{cfg['num_experts']} experts")
        return cls(
            **{k: int(cfg[k]) for k in ints},
            router_width=int(cfg.get("router_width", cfg["num_experts"])),
            held_from=int(lo),
            partial_rotary_factor=float(cfg["partial_rotary_factor"]),
            rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            dtype=str(cfg.get("torch_dtype", "bfloat16")),
            init_std=float(cfg.get("initializer_range", 0.02)),
        )

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def is_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0


# -- the parameters, in construction order ------------------------------------


def block_leaves(a: Arch, layer: int):
    """One block's leaves in construction order: ``(name, shape, drawn)``;
    a leaf that is not drawn starts at one (the norm scales).  Matrices
    of a linear layer are (out, in); the expert stacks are (experts held,
    in, out) for gate and up and (experts held, out-of-gate, in-of-model)
    for down, as the program holds them."""
    d = a.hidden_size
    out = [("input_norm.weight", (d,), False)]
    if a.is_attention(layer):
        h, kv, hd = a.num_attention_heads, a.num_key_value_heads, a.head_dim
        out += [("mixer.wq.weight", (h * 2 * hd, d), True),
                ("mixer.wk.weight", (kv * hd, d), True),
                ("mixer.wv.weight", (kv * hd, d), True),
                ("mixer.wo.weight", (d, h * hd), True),
                ("mixer.q_norm.weight", (hd,), False),
                ("mixer.k_norm.weight", (hd,), False)]
    else:
        hv = a.linear_num_value_heads
        out += [("mixer.in_proj_qkvz.weight", (a.conv_dim + a.value_dim, d), True),
                ("mixer.in_proj_ba.weight", (2 * hv, d), True),
                ("mixer.conv_weight", (a.conv_dim, a.linear_conv_kernel_dim), True),
                ("mixer.dt_bias", (hv,), True),
                ("mixer.A_log", (hv,), True),
                ("mixer.norm.weight", (a.linear_value_head_dim,), False),
                ("mixer.out_proj.weight", (d, a.value_dim), True)]
    e, f, fs = a.num_experts, a.moe_intermediate_size, a.shared_expert_intermediate_size
    out += [("post_norm.weight", (d,), False),
            ("mlp.router.weight", (a.router_width, d), True),
            ("mlp.w_gate", (e, d, f), True),
            ("mlp.w_up", (e, d, f), True),
            ("mlp.w_down", (e, f, d), True),
            ("mlp.shared.w_gate.weight", (fs, d), True),
            ("mlp.shared.w_up.weight", (fs, d), True),
            ("mlp.shared.w_down.weight", (d, fs), True),
            ("mlp.shared_gate.weight", (1, d), True)]
    return out


def leaf_plan(a: Arch):
    """Every parameter as ``(name, shape, counter)``; ``counter`` is None
    for a norm scale (ones), else the leaf's number in the key stream."""
    plan = [("tok_emb.weight", (a.vocab_size, a.hidden_size), 0)]
    c = 1
    for layer in range(a.num_hidden_layers):
        for name, shape, drawn in block_leaves(a, layer):
            plan.append((f"blocks.{layer}.{name}", shape, c if drawn else None))
            c += int(drawn)
    plan.append(("norm.weight", (a.hidden_size,), None))
    plan.append(("lm_head.weight", (a.vocab_size, a.hidden_size), c))
    return plan


def block_weights_from_seed(a: Arch, seed: int, layer: int, plan=None) -> dict:
    """The leaves of block ``layer`` by their names within the block."""
    pre = f"blocks.{layer}."
    return {name[len(pre):]: leaf(a, seed, counter, shape)
            for name, shape, counter in (plan or leaf_plan(a))
            if name.startswith(pre)}


# -- the mathematics --------------------------------------------------------


def rope(a: Arch, x):
    """x (B, T, H, D): the first ``rotary_dim`` lanes rotated in the
    rotate-half pairing ``(i, i + rotary_dim / 2)``, the rest as they are."""
    r, t = a.rotary_dim, x.shape[1]
    inv = 1.0 / (a.rope_theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv)  # (T, r/2)
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s, x[..., r:]], axis=-1)


def attention(a: Arch, precision: str, u, w):
    """Gated causal softmax attention, float32, the KV heads shared by
    their groups of query heads.  A head of a row at a time: the scores
    are T x T."""
    b, t, _ = u.shape
    h, kv, hd = a.num_attention_heads, a.num_key_value_heads, a.head_dim
    eps = a.rms_norm_eps
    qg = linear(u, w["mixer.wq.weight"], precision).reshape(b, t, h, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = linear(u, w["mixer.wk.weight"], precision).reshape(b, t, kv, hd)
    v = linear(u, w["mixer.wv.weight"], precision).reshape(b, t, kv, hd)
    q = rope(a, rms_norm(q, w["mixer.q_norm.weight"], eps))
    k = rope(a, rms_norm(k, w["mixer.k_norm.weight"], eps))
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

    o = jax.lax.map(one_row, (q, k, v)) * jax.nn.sigmoid(gate)
    return linear(o.reshape(b, t, h * hd), w["mixer.wo.weight"], precision)


def gated_delta_net(a: Arch, precision: str, u, w, drop_at):
    """The Gated-DeltaNet mixer on the normed ``u`` (B, T, D).
    ``drop_at`` (B,) int32: the planted fault's position a row (the state
    is dropped before that token), or a position the row never reaches."""
    b, t, _ = u.shape
    hk, hv = a.linear_num_key_heads, a.linear_num_value_heads
    dk, dv, kw = a.linear_key_head_dim, a.linear_value_head_dim, a.linear_conv_kernel_dim
    c = a.conv_dim
    f32 = lambda name: w[name].astype(jnp.float32)  # noqa: E731
    qkvz = linear(u, w["mixer.in_proj_qkvz.weight"], precision)
    qkv, z = qkvz[..., :c], qkvz[..., c:]
    ba = linear(u, w["mixer.in_proj_ba.weight"], precision)
    # the causal depthwise convolution: output t reads inputs t-(K-1) .. t
    pos = jnp.arange(t)
    after = pos[None, :] >= drop_at[:, None]  # (B, T): output at or past the fault
    ext = jnp.concatenate([jnp.zeros((b, kw - 1, c), jnp.float32), qkv], axis=1)
    acc = jnp.zeros((b, t, c), jnp.float32)
    for j in range(kw):
        src = pos - (kw - 1) + j  # the input position this tap reads
        lost = after & (src[None, :] < drop_at[:, None])
        tap = jnp.where(lost[..., None], 0.0, ext[:, j:j + t])
        acc = acc + f32("mixer.conv_weight")[:, j] * tap
    qkv = jax.nn.silu(acc)

    def l2norm(x):
        x = x.reshape(b, t, hk, dk)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(x, hv // hk, axis=2)  # a key head for each value head

    q = l2norm(qkv[..., :a.key_dim]) / math.sqrt(dk)
    k = l2norm(qkv[..., a.key_dim:2 * a.key_dim])
    v = qkv[..., 2 * a.key_dim:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(f32("mixer.A_log")) * jax.nn.softplus(
        ba[..., hv:] + f32("mixer.dt_bias"))

    def step(s, row):
        i, q_t, k_t, v_t, g_t, b_t = row  # (B, Hv, Dk) x 2, (B, Hv, Dv), (B, Hv) x 2
        s = jnp.where((i == drop_at)[:, None, None, None], 0.0, s)
        s = jnp.exp(g_t)[..., None, None] * s
        ks = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=HIGHEST)
        d = b_t[..., None] * (v_t - ks)
        s = s + k_t[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=HIGHEST)

    rows = (pos,) + tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), jnp.float32), rows)
    o = jnp.moveaxis(o, 0, 1)  # (B, T, Hv, Dv)
    y = rms_norm(o, w["mixer.norm.weight"], a.rms_norm_eps) * jax.nn.silu(
        z.reshape(b, t, hv, dv))
    return linear(y.reshape(b, t, hv * dv), w["mixer.out_proj.weight"], precision)


def swiglu(x, w_gate, w_up, w_down, precision):
    """Matrices (out, in), as ``linear`` takes them."""
    h = jax.nn.silu(linear(x, w_gate, precision)) * linear(x, w_up, precision)
    return linear(h, w_down, precision)


def experts(a: Arch, precision: str, x, w):
    """The expert layer's share on the normed ``x`` (B, T, D): every
    HELD expert on every token, summed under the router's weights (zero
    where the expert was not chosen; the weights renormalised over all
    the chosen, held here or not), plus the gated shared expert."""
    probs = jax.nn.softmax(linear(x, w["mlp.router.weight"], precision), axis=-1)
    picked, chosen = jax.lax.top_k(probs, a.num_experts_per_tok)
    picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(chosen, a.router_width, dtype=jnp.float32)
    weights = jnp.einsum("btk,btke->bte", picked, onehot)  # (B, T, router_width)
    weights = weights[..., a.held_from:a.held_from + a.num_experts]

    def add_expert(y, ew):
        w_gate, w_up, w_down, we = ew  # (D, F), (D, F), (F, D), (B, T)
        out = swiglu(x, w_gate.T, w_up.T, w_down.T, precision)
        return y + we[..., None] * out, None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (w["mlp.w_gate"], w["mlp.w_up"], w["mlp.w_down"],
         jnp.moveaxis(weights, -1, 0)))
    shared = swiglu(x, w["mlp.shared.w_gate.weight"], w["mlp.shared.w_up.weight"],
                    w["mlp.shared.w_down.weight"], precision)
    gate = jax.nn.sigmoid(linear(x, w["mlp.shared_gate.weight"], precision))
    return y + gate * shared


def block(a: Arch, precision: str, is_attention: bool, x, w, drop_at):
    """One decoder block.  x (B, T, D) float32; ``w`` maps a block's leaf
    names (without the ``blocks.N.`` prefix) to arrays."""
    u = rms_norm(x, w["input_norm.weight"], a.rms_norm_eps)
    if is_attention:
        x = x + attention(a, precision, u, w)
    else:
        x = x + gated_delta_net(a, precision, u, w, drop_at)
    u = rms_norm(x, w["post_norm.weight"], a.rms_norm_eps)
    return x + experts(a, precision, u, w)


def head_logits(a: Arch, precision: str, x, norm_w, head_w):
    return linear(rms_norm(x, norm_w, a.rms_norm_eps), head_w, precision)


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
        self._delta = jax.jit(
            lambda x, w, at: block(a, precision, False, x, w, at))
        self._head = jax.jit(
            lambda x, norm_w, head_w: head_logits(a, precision, x, norm_w, head_w))

    def hidden(self, tokens):
        a = self.a
        tokens = jnp.asarray(tokens, jnp.int32)
        never = tokens.shape[1]  # a position no row reaches
        drop_at = jnp.full((tokens.shape[0],), never, jnp.int32) if (
            self.drop_state_at is None) else jnp.asarray(
                self.drop_state_at, jnp.int32)
        emb = leaf(a, self.seed, 0, (a.vocab_size, a.hidden_size))
        x = self._embed(emb, tokens)
        del emb
        for layer in range(a.num_hidden_layers):
            w = block_weights_from_seed(a, self.seed, layer, self.plan)
            step = self._attention if a.is_attention(layer) else self._delta
            x = step(x, w, drop_at)
            del w
        return x

    def logits_rows(self, tokens):
        """Yield (row index, (T, vocab) float32 device array)."""
        a = self.a
        x = self.hidden(tokens)
        _, shape, counter = self.plan[-1]
        head_w = leaf(a, self.seed, counter, shape)
        norm_w = jnp.ones((a.hidden_size,), a.jdtype)
        for i in range(x.shape[0]):
            yield i, self._head(x[i:i + 1], norm_w, head_w)[0]
