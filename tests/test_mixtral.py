"""Mixtral (sparse-MoE decoder) model family: deferred init parity,
dense-vs-capacity routing agreement, cached decode, EP-sharded training."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import torchdistx_tpu as tdx
from torchdistx_tpu.models import Mixtral
from torchdistx_tpu.nn import functional, functional_call
from torchdistx_tpu.parallel import create_mesh


def _tokens(b=2, s=32, vocab=256, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, vocab, (b, s)), jnp.int32
    )


def test_deferred_matches_eager_init():
    tdx.manual_seed(11)
    m_def = tdx.deferred_init(Mixtral.from_name, "tiny")
    assert tdx.is_deferred(m_def)
    tdx.materialize_module(m_def)
    tdx.manual_seed(11)
    m_eager = Mixtral.from_name("tiny")
    p_def = dict(m_def.named_parameters())
    p_eager = dict(m_eager.named_parameters())
    assert p_def.keys() == p_eager.keys()
    for name, a in p_def.items():
        assert np.array_equal(np.asarray(a), np.asarray(p_eager[name])), name


def test_forward_and_aux_loss():
    tdx.manual_seed(12)
    m = Mixtral.from_name("tiny")
    tok = _tokens()
    logits = m(tok)
    assert logits.shape == (2, 32, 256)
    logits2, aux = m.forward_with_aux(tok)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits2))
    # balanced routing has aux ~1; pathological collapse drives it to E
    assert 0.5 < float(aux) < m.cfg.n_experts


def test_capacity_matches_dense_when_sufficient():
    tdx.manual_seed(13)
    m_dense = Mixtral.from_name("tiny")
    tdx.manual_seed(13)
    m_cap = Mixtral.from_name(
        "tiny",
        # capacity >= E/top_k: no token can be dropped -> exact agreement
        capacity_factor=float(4 / 2),
    )
    tok = _tokens(seed=3)
    np.testing.assert_allclose(
        np.asarray(m_dense(tok)), np.asarray(m_cap(tok)), rtol=2e-5, atol=2e-5
    )


def test_cached_decode_matches_full_forward():
    tdx.manual_seed(14)
    m = Mixtral.from_name("tiny")
    tok = _tokens(b=1, s=16, seed=5)
    full = m(tok)
    cache = m.init_cache(1, max_seq=32)
    # prefill 12, then decode 4 one at a time
    logits, cache = m.forward_cached(tok[:, :12], cache, 0)
    np.testing.assert_allclose(
        np.asarray(full[:, :12]), np.asarray(logits), rtol=2e-5, atol=2e-5
    )
    for i in range(12, 16):
        logits, cache = m.forward_cached(tok[:, i : i + 1], cache, i)
        np.testing.assert_allclose(
            np.asarray(full[:, i : i + 1]),
            np.asarray(logits),
            rtol=2e-5,
            atol=2e-5,
        )


def test_ep_sharded_train_step_matches_unsharded():
    mesh = create_mesh({"dp": 2, "ep": 4})
    tdx.manual_seed(15)
    m = tdx.deferred_init(Mixtral.from_name, "tiny")
    tdx.materialize_module(m, sharding_rule=m.shard_rule(mesh))
    params = dict(m.named_parameters())
    w = params["blocks.0.mlp.w_gate"]
    assert w.sharding.spec == P("ep", None, None)

    tok, labels = _tokens(seed=7), _tokens(seed=8)
    tx = optax.sgd(1e-2)

    def loss_fn(p):
        logits, aux = functional_call(
            m, p, (tok,), method="forward_with_aux"
        )
        return functional.cross_entropy(logits, labels) + 1e-2 * aux

    @jax.jit
    def step(p, s):
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, s = tx.update(grads, s, p)
        return jax.tree_util.tree_map(lambda a, u: a + u, p, updates), s, loss

    p1, s1, loss_sharded = step(params, tx.init(params))

    # same math fully replicated
    rep = jax.device_put(params, NamedSharding(mesh, P()))
    p2, s2, loss_rep = step(rep, tx.init(rep))
    np.testing.assert_allclose(
        float(loss_sharded), float(loss_rep), rtol=1e-5
    )
    for name in ("blocks.0.mlp.w_down", "lm_head.weight"):
        np.testing.assert_allclose(
            np.asarray(p1[name]), np.asarray(p2[name]), rtol=2e-5, atol=2e-5
        )


@pytest.mark.slow
def test_generate_greedy_matches_full_recompute():
    tdx.manual_seed(16)
    m = Mixtral.from_name("tiny")
    prompt = _tokens(b=1, s=8, seed=9)
    out = tdx.generate(m, prompt, max_new_tokens=5)
    assert out.shape == (1, 13)
    # greedy decode must equal argmax over the full (uncached) forward
    cur = prompt
    for _ in range(5):
        nxt = jnp.argmax(m(cur)[:, -1], axis=-1)[:, None]
        cur = jnp.concatenate([cur, nxt.astype(cur.dtype)], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(cur))


def test_remat_matches_no_remat():
    tdx.manual_seed(17)
    m = Mixtral.from_name("tiny")
    tdx.manual_seed(17)
    m_remat = Mixtral.from_name("tiny", remat=True)
    tok = _tokens(seed=10)
    np.testing.assert_allclose(
        np.asarray(m(tok)), np.asarray(m_remat(tok)), rtol=1e-6, atol=1e-6
    )
    la, aa = m.forward_with_aux(tok)
    lb, ab = m_remat.forward_with_aux(tok)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(aa), float(ab), rtol=1e-6)
    # gradients flow through the rematted aux path
    p = dict(m_remat.named_parameters())
    g = jax.grad(
        lambda pp: functional.cross_entropy(
            functional_call(m_remat, pp, (tok,)), tok
        )
    )(p)
    assert float(jnp.abs(g["blocks.0.mlp.w_gate"]).sum()) > 0


def test_grouped_dispatch_matches_dense():
    """``moe_dispatch="grouped"`` (no token dropped, work ~ tokens x
    top_k: the path the DeepSeek-V3 family serves on) is the dense
    compute's result: same weights, same routing, float32 sums in
    another order."""
    tdx.manual_seed(13)
    m_dense = Mixtral.from_name("tiny")
    tdx.manual_seed(13)
    m_grouped = Mixtral.from_name("tiny", moe_dispatch="grouped")
    tok = _tokens(seed=3)
    np.testing.assert_allclose(
        np.asarray(m_dense(tok)), np.asarray(m_grouped(tok)),
        rtol=2e-5, atol=2e-5,
    )
