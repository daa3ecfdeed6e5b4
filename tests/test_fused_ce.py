"""Fused LM-head cross-entropy parity (interpret mode; compiled acceptance
is captured by scripts/verify_kernels_onchip.py's fusedce phase).

Spec: fused_linear_cross_entropy(x, w, y) == cross_entropy(x @ w.T, y)
in value and in (dx, dw) gradients, for bf16 and f32, odd shapes, and
every label position (first/last vocab tile)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu.nn import functional
from torchdistx_tpu.ops.fused_ce import fused_linear_cross_entropy


def _ref(x, w, labels):
    return functional.cross_entropy(
        jnp.einsum("nd,vd->nv", x, w), labels
    )


def _mk(n, d, v, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k[0], (n, d), dtype)
    w = jax.random.normal(k[1], (v, d), dtype) * 0.1
    y = jax.random.randint(k[2], (n,), 0, v)
    return x, w, y


@pytest.mark.parametrize(
    "n,d,v,dtype",
    [
        (256, 128, 512, jnp.float32),
        (256, 128, 512, jnp.bfloat16),
        (384, 64, 1000, jnp.float32),  # odd token/vocab block shrink
        (64, 256, 2048, jnp.bfloat16),
    ],
)
def test_loss_and_grads_match_reference(n, d, v, dtype):
    x, w, y = _mk(n, d, v, dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5

    loss_f = fused_linear_cross_entropy(x, w, y)
    loss_r = _ref(x, w, y)
    np.testing.assert_allclose(
        float(loss_f), float(loss_r), rtol=tol, atol=tol
    )

    gx_f, gw_f = jax.grad(
        lambda x, w: fused_linear_cross_entropy(x, w, y), argnums=(0, 1)
    )(x, w)
    gx_r, gw_r = jax.grad(
        lambda x, w: _ref(x, w, y), argnums=(0, 1)
    )(x, w)
    for a, b in ((gx_f, gx_r), (gw_f, gw_r)):
        scale = np.max(np.abs(np.asarray(b, np.float32))) + 1e-8
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale,
            np.asarray(b, np.float32) / scale,
            atol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
        )


def test_leading_dims_flattened():
    x, w, y = _mk(128, 64, 256, jnp.float32, seed=1)
    x3 = x.reshape(4, 32, 64)
    y3 = y.reshape(4, 32)
    a = fused_linear_cross_entropy(x3, w, y3)
    b = fused_linear_cross_entropy(x, w, y)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)


def test_labels_at_tile_edges():
    # labels in the first and last columns of first/last vocab tiles: the
    # in-tile one-hot match must catch each exactly once
    n, d, v = 8, 32, 512
    x, w, _ = _mk(n, d, v, jnp.float32, seed=2)
    y = jnp.asarray([0, 1, 127, 128, 255, 256, 510, 511])
    loss_f = fused_linear_cross_entropy(x, w, y, block_v=128)
    np.testing.assert_allclose(float(loss_f), float(_ref(x, w, y)), rtol=1e-5)


def test_cotangent_scaling():
    x, w, y = _mk(64, 32, 128, jnp.float32, seed=3)
    g2 = jax.grad(lambda x: 2.0 * fused_linear_cross_entropy(x, w, y))(x)
    g1 = jax.grad(lambda x: fused_linear_cross_entropy(x, w, y))(x)
    np.testing.assert_allclose(
        np.asarray(g2), 2.0 * np.asarray(g1), rtol=1e-5
    )


def test_shape_validation():
    x, w, y = _mk(64, 32, 128, jnp.float32)
    with pytest.raises(ValueError, match="w must be"):
        fused_linear_cross_entropy(x, w.T, y)
    with pytest.raises(ValueError, match="labels"):
        fused_linear_cross_entropy(x, w, y[:-1])


@pytest.mark.parametrize("family", ["gpt2", "t5"])
def test_model_hidden_path_matches_logits(family):
    # return_hidden + fused CE == cross_entropy(model logits) for the
    # tied-head families (GPT-2 plain tie, T5 scaled tie)
    import torchdistx_tpu as tdx

    tdx.manual_seed(0)
    if family == "gpt2":
        from torchdistx_tpu.models import GPT2

        m = tdx.deferred_init(GPT2.from_name, "tiny")
        tdx.materialize_module(m)
        p = dict(m.named_parameters())
        toks = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 64)
        args = (toks,)
        w_key = "tok_emb.weight"
    else:
        from torchdistx_tpu.models import T5

        m = tdx.deferred_init(T5.from_name, "tiny")
        tdx.materialize_module(m)
        p = dict(m.named_parameters())
        toks = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 64)
        args = (toks, toks)
        w_key = "shared_emb.weight"
    from torchdistx_tpu.nn import functional_call

    h = functional_call(m, p, args, {"return_hidden": True})
    fused = fused_linear_cross_entropy(h, p[w_key], toks)
    ref = functional.cross_entropy(functional_call(m, p, args), toks)
    np.testing.assert_allclose(float(fused), float(ref), rtol=1e-4)


def test_sequence_parallel_shard_map(mesh8):
    # per-shard fused CE + pmean == global CE (equal shard sizes), in
    # value and in grads — the loss SP training composes with
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n, d, v = 512, 64, 256
    x, w, y = _mk(n, d, v, jnp.float32, seed=4)

    def local_loss(x, w, y):
        return jax.lax.pmean(fused_linear_cross_entropy(x, w, y), "fsdp")

    def sm(f):
        return shard_map(
            f, mesh=mesh8, in_specs=(P("fsdp"), P(), P("fsdp")),
            out_specs=P(), check_vma=False,
        )

    loss_sp = jax.jit(sm(local_loss))(x, w, y)
    np.testing.assert_allclose(float(loss_sp), float(_ref(x, w, y)),
                               rtol=1e-6)
    g_sp = jax.jit(jax.grad(
        lambda x, w: sm(local_loss)(x, w, y), argnums=(0, 1)
    ))(x, w)
    g_ref = jax.grad(
        lambda x, w: _ref(x, w, y), argnums=(0, 1)
    )(x, w)
    for a, b in zip(g_sp, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_gpt2_vocab_padding():
    # 50257 has no good block divisor (7*43*167): _blocks must pad the
    # vocab instead of shrinking block_v to 1 (a 50k-step grid), and the
    # padded columns must vanish from the loss and both gradients
    from torchdistx_tpu.ops.fused_ce import _blocks

    bt, bv, n_t, n_v, v_pad, n_pad = _blocks(64, 50257, 256, 512)
    assert bv == 512 and v_pad == 50688 and n_v == 99 and n_pad == 64

    n, d, v = 64, 32, 50257
    x, w, _ = _mk(n, d, v, jnp.float32, seed=6)
    y = jnp.concatenate([
        jnp.asarray([0, 50256, 50255]),  # last true columns
        jax.random.randint(jax.random.PRNGKey(7), (n - 3,), 0, v),
    ])
    loss_f = fused_linear_cross_entropy(x, w, y)
    np.testing.assert_allclose(float(loss_f), float(_ref(x, w, y)),
                               rtol=1e-5)
    gx_f, gw_f = jax.grad(
        lambda x, w: fused_linear_cross_entropy(x, w, y), argnums=(0, 1)
    )(x, w)
    gx_r, gw_r = jax.grad(
        lambda x, w: _ref(x, w, y), argnums=(0, 1)
    )(x, w)
    assert gw_f.shape == (v, d)  # sliced back to the true vocab
    for a, b in ((gx_f, gx_r), (gw_f, gw_r)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        )


def test_prime_token_count_padding():
    # 509 tokens (prime) would shrink block_t to 1; the token dim pads
    # instead, with padded rows masked out of the loss mean and both
    # gradients
    from torchdistx_tpu.ops.fused_ce import _blocks

    bt, bv, n_t, n_v, v_pad, n_pad = _blocks(509, 512, 256, 512)
    assert bt == 256 and n_pad == 512 and n_t == 2

    n, d, v = 509, 32, 512
    x, w, y = _mk(n, d, v, jnp.float32, seed=8)
    loss_f = fused_linear_cross_entropy(x, w, y)
    np.testing.assert_allclose(float(loss_f), float(_ref(x, w, y)),
                               rtol=1e-5)
    gx_f, gw_f = jax.grad(
        lambda x, w: fused_linear_cross_entropy(x, w, y), argnums=(0, 1)
    )(x, w)
    gx_r, gw_r = jax.grad(
        lambda x, w: _ref(x, w, y), argnums=(0, 1)
    )(x, w)
    assert gx_f.shape == (n, d)
    for a, b in ((gx_f, gx_r), (gw_f, gw_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_tiny_token_count_pads_to_sublane_minimum():
    # n < 8 divides itself, so neither the shrink nor the n > 8 padding
    # path fired — compiled Mosaic would get a <8-sublane block.  _blocks
    # must pad tiny token counts up to one 8-row block, and the padded
    # rows must vanish from the loss mean and both gradients
    from torchdistx_tpu.ops.fused_ce import _blocks

    for n in (1, 3, 7):
        bt, bv, n_t, n_v, v_pad, n_pad = _blocks(n, 512, 256, 512)
        assert bt == 8 and n_pad == 8 and n_t == 1
    bt, _, n_t, _, _, n_pad = _blocks(8, 512, 256, 512)
    assert bt == 8 and n_pad == 8 and n_t == 1  # exactly 8 needs no pad

    n, d, v = 3, 32, 512
    x, w, y = _mk(n, d, v, jnp.float32, seed=9)
    loss_f = fused_linear_cross_entropy(x, w, y)
    np.testing.assert_allclose(float(loss_f), float(_ref(x, w, y)),
                               rtol=1e-5)
    gx_f, gw_f = jax.grad(
        lambda x, w: fused_linear_cross_entropy(x, w, y), argnums=(0, 1)
    )(x, w)
    gx_r, gw_r = jax.grad(
        lambda x, w: _ref(x, w, y), argnums=(0, 1)
    )(x, w)
    assert gx_f.shape == (n, d)
    for a, b in ((gx_f, gx_r), (gw_f, gw_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
