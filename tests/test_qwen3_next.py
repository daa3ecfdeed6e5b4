"""Qwen3-Next family (models/qwen3_next.py): Gated-DeltaNet layers whose
matrix state lives beside the KV rows of the gated attention layers,
every feed-forward an expert layer that holds a SHARE of its experts,
served by ``generate()`` and ``ServeEngine`` — against the family's
PLAIN REFERENCE (benchmarks/families/qwen3_next_reference.py: float32
``jax.numpy``, the whole sequence from empty state, the delta rule a
``lax.scan`` over tokens, the held experts a masked sum, its own weights
from the seed; it imports nothing of the program).  The size is the
rehearsal's: one period of three Gated-DeltaNet layers and an attention
layer, experts 8-15 of 32 held.

Tolerances, float32 on both sides at the tiny size (logits of order
0.1): the program adds the same float32 products in another order (the
chunked delta rule against the token-by-token one, grouped tiles
against a masked sum over experts), which reads 1e-7 … 4e-7 here —
``TOL`` = 5e-6 leaves a decimal of room and is far under what the same
program gives with its weights rounded to bfloat16 (pinned below).
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import torchdistx_tpu as tdx
from torchdistx_tpu.generation import generate
from torchdistx_tpu.models import Qwen3Next, Qwen3NextConfig
from torchdistx_tpu.nn import functional_call
from torchdistx_tpu.serve import ServeEngine
from torchdistx_tpu.serve.kv_cache import RecurrentState, entry_kind

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"
)
TOL = 5e-6
SEED = 2**31 + 19  # the driver's seeds pass 31 bits


@pytest.fixture(scope="module")
def family():
    """The benchmark's family module, as ``harness.loader`` loads it."""
    sys.path.insert(0, BENCH)
    try:
        from harness import loader

        yield loader.load_family(
            "qwen3_next", needs=("reference.ServeReference",)
        )
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def config():
    path = os.path.join(
        BENCH, "rehearsal", "configs-qwen3_next", "tiny-qwen3-next.json"
    )
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model(family, config):
    """The program's model as the benchmark's driver makes it: seed ->
    ``deferred_init`` -> ``materialize_module``."""
    from harness import reference

    tdx.manual_seed(reference.seed31(SEED))
    m = tdx.deferred_init(family.constructor(config))
    assert tdx.is_deferred(m)
    tdx.materialize_module(m)
    return m


@pytest.fixture(scope="module")
def ref(family, config):
    arch = family.reference.Arch.from_config(config)
    return family.reference.ServeReference(arch, SEED, "f32")


def _tokens(b, s, seed=0, vocab=256):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, vocab, (b, s)), jnp.int32
    )


def _ref_logits(ref, tokens):
    return np.stack([np.asarray(row) for _, row in ref.logits_rows(tokens)])


def _gaps(ref, prompts, outputs):
    """The widest gap by which a served token's reference logit lies
    under the reference's best, over all requests."""
    worst = 0.0
    for p, o in zip(prompts, outputs):
        seq = np.concatenate([p, o])[None]
        logits = _ref_logits(ref, seq)[0]
        for j in range(len(p) - 1, len(seq[0]) - 1):
            worst = max(worst, float(logits[j].max() - logits[j, seq[0, j + 1]]))
    return worst


def _requests():
    lens, news = (5, 20, 11, 30, 16), (6, 9, 4, 7, 12)
    return [
        {"prompt": np.asarray(_tokens(1, n, seed=10 + i))[0],
         "max_new_tokens": k}
        for i, (n, k) in enumerate(zip(lens, news))
    ]


def _states(cache):
    return [e for e in cache if isinstance(e, RecurrentState)]


# -- program against the plain reference ---------------------------------------


def test_leaves_are_the_seeds_rule_bit_for_bit(family, model, config):
    from harness import reference

    arch = family.reference.Arch.from_config(config)
    plan = family.reference.leaf_plan(arch)
    params = dict(model.named_parameters())
    assert {name for name, _, _ in plan} == set(params)
    assert params["lm_head.weight"].shape == (256, 64)  # untied
    # the router scores all 32, the stacks hold the share's 8
    assert params["blocks.0.mlp.router.weight"].shape == (32, 64)
    assert params["blocks.0.mlp.w_gate"].shape == (8, 64, 32)
    assert reference.weights_differ(arch, plan, SEED, params) == 0


def test_layer_kinds_follow_the_attention_interval(model):
    kinds = [entry_kind(e) for e in model.init_cache(1, 8)]
    assert kinds == ["state", "state", "state", "pair"]
    big = Qwen3NextConfig()  # Qwen3-Next-80B-A3B: attention every fourth
    assert [i for i in range(8) if big.is_attention(i)] == [3, 7]
    assert (big.key_dim, big.value_dim, big.conv_dim) == (2048, 4096, 8192)
    assert big.rotary_dim == 64
    state = jax.eval_shape(lambda: Qwen3Next(big).init_cache(1, 8)[0])
    assert state.ssm.shape == (1, 32, 128, 128)
    assert state.ssm.dtype == jnp.float32
    assert state.conv.shape == (1, 3 * 8192)


def test_forward_matches_the_reference(model, ref):
    tokens = _tokens(2, 40)
    want = _ref_logits(ref, tokens)
    got = np.asarray(model(tokens))
    assert np.abs(want).max() > 0.05  # not a comparison of zeros
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    # the next precision down fails the same comparison by far
    low = functional_call(
        model,
        {k: v.astype(jnp.bfloat16) for k, v in model.named_parameters()},
        (tokens,),
    )
    assert np.abs(np.asarray(low, np.float32) - want).max() > 100 * TOL


def test_prefill_then_decode_is_the_full_forward(model, ref):
    """Both kinds of state carried: 13 tokens prefilled, 8 decoded one
    by one through ``forward_cached``, logits against the reference's
    whole-sequence forward."""
    tokens = _tokens(2, 21, seed=1)
    want = _ref_logits(ref, tokens)
    cache = model.init_cache(2, 32)
    logits, cache = model.forward_cached(tokens[:, :13], cache, 0)
    np.testing.assert_allclose(np.asarray(logits), want[:, :13], rtol=0, atol=TOL)
    for t in range(13, 21):
        logits, cache = model.forward_cached(tokens[:, t:t + 1], cache, t)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), want[:, t], rtol=0, atol=TOL
        )
    assert isinstance(cache[0], RecurrentState)
    assert cache[0].ssm.ndim == 4 and cache[0].ssm.dtype == jnp.float32


def test_slot_decode_is_the_full_forward(model, ref):
    """The serve engine's step (``forward_decode`` over the STORED
    layout, every row at its own depth) after prefills of unequal
    length."""
    from torchdistx_tpu.serve.kv_cache import SlotKVCache, write_slot

    a, b = _tokens(1, 19, seed=2), _tokens(1, 12, seed=3)
    want_a, want_b = _ref_logits(ref, a)[0], _ref_logits(ref, b)[0]
    kv = SlotKVCache(model, 2, 32).kv
    for slot, (seq, n) in enumerate(((a, 9), (b, 5))):
        _, slab = model.forward_cached(
            jnp.pad(seq[:, :n], ((0, 0), (0, 16 - n))), model.init_cache(1, 16),
            0, logits_at=n - 1,
        )
        kv = write_slot(kv, slab, slot)
    for i in range(7):
        toks = jnp.stack([a[0, 9 + i], b[0, 5 + i]])[:, None]
        pos = jnp.asarray([9 + i, 5 + i], jnp.int32)
        logits, kv = model.forward_decode(toks, kv, pos)
        np.testing.assert_allclose(
            np.asarray(logits[0, 0]), want_a[9 + i], rtol=0, atol=TOL
        )
        np.testing.assert_allclose(
            np.asarray(logits[1, 0]), want_b[5 + i], rtol=0, atol=TOL
        )


@pytest.mark.parametrize("n", [11, 2, 16])
def test_same_prompt_in_two_buckets_writes_the_same_state(model, ref, n):
    """Padding rows must leave ``S`` and ``conv`` untouched: the state
    is the state after ``n`` REAL tokens (fewer than the convolution's
    3 of history, and a full bucket, among them)."""
    prompt = _tokens(1, n, seed=4)
    want = _ref_logits(ref, prompt)[0, n - 1]
    out = []
    for bucket in (16, 32):
        logits, slab = model.forward_cached(
            jnp.pad(prompt, ((0, 0), (0, bucket - n))),
            model.init_cache(1, bucket), 0, logits_at=n - 1,
        )
        assert logits.shape == (1, 1, 256)  # the sampled position only
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want, rtol=0, atol=TOL)
        out.append(_states(slab))
    for s16, s32 in zip(*out):
        assert np.abs(np.asarray(s16.ssm)).max() > 1e-4  # a state was written
        np.testing.assert_allclose(s16.ssm, s32.ssm, rtol=0, atol=TOL)
        np.testing.assert_allclose(s16.conv, s32.conv, rtol=0, atol=TOL)
    # the exact prompt, unpadded, through the plain path: the same state
    _, exact = model.forward_cached(prompt, model.init_cache(1, n), 0)
    for e, s in zip(_states(exact), out[0]):
        np.testing.assert_allclose(e.ssm, s.ssm, rtol=0, atol=TOL)
        np.testing.assert_allclose(e.conv, s.conv, rtol=0, atol=TOL)


def test_kernels_in_the_model_match_the_jnp_forms(model):
    """``use_flash=True`` off the chip: both delta-rule kernels, the
    grouped matmul over the share and the attention kernels at a gated,
    partly rotated head, in interpret mode, against the jnp model."""
    cfg = Qwen3NextConfig(**{**vars(model.cfg), "use_flash": True})
    kernels = Qwen3Next(cfg)
    params = dict(model.named_parameters())
    tokens = _tokens(1, 24, seed=6)
    want = np.asarray(model(tokens))
    cache = kernels.init_cache(1, 128)
    logits, cache = functional_call(
        kernels, params, (jnp.pad(tokens[:, :17], ((0, 0), (0, 15))), cache, 0),
        {"logits_at": 16}, method="forward_cached",
    )
    np.testing.assert_allclose(np.asarray(logits[0, 0]), want[0, 16], rtol=0, atol=TOL)
    from torchdistx_tpu.serve.kv_cache import merge_heads

    stored = [
        e if isinstance(e, RecurrentState) else tuple(merge_heads(a) for a in e)
        for e in cache
    ]
    for t in range(17, 24):
        logits, stored = functional_call(
            kernels, params,
            (tokens[:, t:t + 1], stored, jnp.asarray([t], jnp.int32)),
            method="forward_decode",
        )
        np.testing.assert_allclose(
            np.asarray(logits[0, 0]), want[0, t], rtol=0, atol=TOL
        )


def test_rope_turns_the_first_quarter_of_a_head_only(model):
    from torchdistx_tpu.models.qwen3_next import _rope_head

    rope = model._rope()
    assert rope.shape[1] * 2 == model.cfg.rotary_dim == 8
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 4, 32))
    y = _rope_head(x, rope, 3)
    np.testing.assert_array_equal(np.asarray(y[..., 8:]), np.asarray(x[..., 8:]))
    assert np.abs(np.asarray(y[..., :8] - x[..., :8])).max() > 0.1
    at = _rope_head(x[:, :1], rope, positions=jnp.asarray([3], jnp.int32))
    np.testing.assert_allclose(np.asarray(at), np.asarray(y[:, :1]), atol=1e-6)


def test_generate_serves_what_the_reference_puts_first(model, ref):
    prompt = _tokens(2, 9, seed=7)
    out = np.asarray(generate(model, prompt, 10))
    assert out.shape == (2, 19)
    assert _gaps(ref, list(np.asarray(prompt)), list(out[:, 9:])) <= TOL


class TestServeEngine:
    def test_five_unequal_requests_at_three_slots_equal_generate(self, model, ref):
        """The normal path: scheduler, slab bookkeeping with two kinds
        of entry (a 4-D state among them), bucketed prefills told their
        true length, expert counters riding programs that carry a
        state, slots reused."""
        engine = ServeEngine(
            model, num_slots=3, max_len=64, prefill_buckets=(16, 32)
        )
        assert engine.recurrent and not engine.latent
        assert engine.cache.kv_heads == 2
        reqs = _requests()
        results = engine.run(reqs)
        outputs = [r.tokens for r in results]
        assert [len(o) for o in outputs] == [r["max_new_tokens"] for r in reqs]
        for r, o in zip(reqs, outputs):
            g = generate(model, jnp.asarray(r["prompt"][None]), len(o))
            np.testing.assert_array_equal(g[0, len(r["prompt"]):], o)
        assert _gaps(ref, [r["prompt"] for r in reqs], outputs) <= TOL

    def test_expert_counters_count_the_share_and_the_rest(self, model):
        """``moe_routed_rows`` counts held rows, ``moe_rows_elsewhere``
        the others: together every (token, choice) of every layer the
        programs worked, bucket padding and idle slots included."""
        engine = ServeEngine(
            model, num_slots=2, max_len=64, prefill_buckets=(16,)
        )
        reqs = [dict(r, max_new_tokens=5) for r in _requests()[::2][:2]]
        engine.run(reqs)
        m = engine.metrics
        m.sync_device_counters()
        cnt = m.counters
        layers, top_k = model.cfg.n_layers, model.cfg.top_k
        prefill = cnt["moe_routed_rows_prefill"] + cnt["moe_rows_elsewhere_prefill"]
        assert prefill == 2 * 16 * top_k * layers
        decode = cnt["moe_routed_rows_decode"] + cnt["moe_rows_elsewhere_decode"]
        assert decode == cnt["decode_dispatches"] * 2 * top_k * layers
        # 8 of 32 experts held: about a quarter of the choices, and a
        # touched expert is a held one
        share = cnt["moe_routed_rows"] / (prefill + decode)
        assert 0.1 < share < 0.45
        calls = 2 + cnt["decode_dispatches"]
        assert 0 < cnt["moe_groups"] <= 8 * layers * calls

    def test_a_reused_slot_serves_what_a_fresh_engine_serves(self, model):
        """No state leaks between requests: one slot, a long request and
        then a short one whose prompt is under the convolution's history,
        against the short one alone on a fresh engine."""
        long_req, short_req = _requests()[3], {
            "prompt": np.asarray(_tokens(1, 2, seed=8))[0], "max_new_tokens": 9}
        kw = dict(num_slots=1, max_len=64, prefill_buckets=(16, 32))
        used = ServeEngine(model, **kw)
        first, second = used.run([long_req, short_req])
        assert len(first.tokens) == long_req["max_new_tokens"]
        fresh = ServeEngine(model, **kw).run([short_req])[0]
        np.testing.assert_array_equal(second.tokens, fresh.tokens)

    def test_two_programs_and_no_recompile(self, model):
        engine = ServeEngine(  # a geometry no other test's engine shares
            model, num_slots=2, max_len=48, prefill_buckets=(32,)
        )
        reqs = _requests()
        h0 = engine.submit(reqs[0]["prompt"], max_new_tokens=3)
        h1 = engine.submit(reqs[1]["prompt"], max_new_tokens=12)
        while not h0.done():
            engine.step()
        assert not h1.done()
        warm = engine.num_compiled_programs()
        if warm is None:
            pytest.skip("jit cache introspection unavailable on this jax")
        assert warm == 2  # one prefill bucket + one decode step
        engine.submit(reqs[2]["prompt"], max_new_tokens=6)  # a dirty slot
        while engine.step():
            pass
        assert engine.num_compiled_programs() == warm

    def test_gauges_say_what_a_slot_holds(self, model, family, config):
        cfg = model.cfg
        engine = ServeEngine(
            model, num_slots=2, max_len=64, prefill_buckets=(16, 32)
        )
        engine.run(_requests()[:2])
        gauges = engine.metrics.to_json()["gauges"]
        # 3 Gated-DeltaNet layers: S (4 heads of 16 x 16 float32) and 3
        # conv rows (2 x 32 + 64 lanes, float32 in the toy)
        per_layer = 4 * 16 * 16 * 4 + 3 * cfg.conv_dim * 4
        assert gauges["state_slot_bytes"] == 3 * per_layer
        assert gauges["state_slot_bytes"] == engine.cache.state_slot_bytes
        # the attention layer's rows: K and V of 2 heads of 32, float32
        assert gauges["kv_row_bytes"] == 2 * 2 * 32 * 4
        assert gauges["kv_cache_bytes"] == engine.cache.nbytes == (
            2 * 64 * gauges["kv_row_bytes"] + 2 * gauges["state_slot_bytes"]
        )
        # and at the published widths, by the family's counts: 12.88 MB
        # a slot over the cell's 6 layers, 2048 B a row
        big = json.load(open(os.path.join(
            BENCH, "configs", "qwen3-next-80b-a3b-1chip.json")))
        assert family.counts.state_slot_bytes(big) == 6 * (2097152 + 49152)

    @pytest.mark.parametrize(
        "kwargs,name",
        [
            (dict(page_size=16), "page_size"),
            (dict(page_size=16, prefix_cache=True), "the prefix cache"),
            (dict(kv_dtype="int8"), "kv_dtype='int8'"),
            (dict(speculate=2), "speculate"),
            (dict(decode_mode="persistent"), "decode_mode='persistent'"),
            (dict(chunked_prefill=16), "chunked_prefill"),
            (dict(mesh=object()), "mesh"),
        ],
        ids=lambda v: v if isinstance(v, str) else "",
    )
    def test_refused_options_raise_by_name(self, model, kwargs, name):
        with pytest.raises(ValueError) as err:
            ServeEngine(model, num_slots=2, max_len=64, **kwargs)
        assert name in str(err.value)
        assert "not supported over recurrent state" in str(err.value)

    @pytest.mark.parametrize("move", ["migrate_to", "handoff_to"])
    def test_moves_between_engines_are_refused_by_name(self, model, move):
        kw = dict(num_slots=2, max_len=64, prefill_buckets=(16, 32))
        src, dst = ServeEngine(model, **kw), ServeEngine(model, **kw)
        h = src.submit(_requests()[0]["prompt"], max_new_tokens=8)
        src.step()
        args = (dst,) if move == "migrate_to" else (dst, h._request)
        with pytest.raises(ValueError, match=f"{move}: not supported over recurrent"):
            getattr(src, move)(*args)
        while src.step():  # and the source serves on, untouched
            pass
        assert len(h.result().tokens) == 8


# -- what the model refuses, and what it names ---------------------------------


@pytest.mark.parametrize(
    "kwargs,match",
    [
        (dict(mlp_only_layers=(1,)), "mlp_only_layers"),
        (dict(decoder_sparse_step=2), "decoder_sparse_step"),
        (dict(use_sliding_window=True), "use_sliding_window"),
        (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
        (dict(tie_word_embeddings=True), "tie_word_embeddings"),
        (dict(norm_topk_prob=False), "norm_topk_prob"),
        (dict(mtp_layers=1), "multi-token-prediction"),
    ],
)
def test_config_refuses_by_name(kwargs, match):
    with pytest.raises(ValueError, match=match):
        Qwen3NextConfig(**kwargs)


@pytest.mark.parametrize(
    "key,value",
    [
        ("mlp_only_layers", [0]),
        ("decoder_sparse_step", 2),
        ("use_sliding_window", True),
        ("tie_word_embeddings", True),
        ("norm_topk_prob", False),
        ("hidden_act", "gelu"),
        ("initializer_range", 0.01),
        ("gdn_state_dtype", "bfloat16"),
        ("experts_held", [0, 4]),
    ],
)
def test_family_constructor_refuses_what_it_does_not_pass_on(
    family, config, key, value
):
    with pytest.raises(ValueError, match=key):
        family.constructor({**config, key: value})


def test_paged_decode_is_refused(model):
    with pytest.raises(ValueError, match="paged cache"):
        model.forward_decode(
            jnp.zeros((1, 1), jnp.int32), model.init_cache(1, 8),
            jnp.zeros((1,), jnp.int32), page_tables=jnp.zeros((1, 1)),
        )


def test_scopes_name_the_new_operations(model):
    """``gdn/conv``, ``gdn/chunk`` (a prefill), ``gdn/update`` (a decode
    step) and ``attn/gate`` beside the expert layer's scopes in the
    compiled operations' names."""
    tokens = _tokens(1, 16, seed=9)
    params = dict(model.named_parameters())
    prefill = jax.jit(
        lambda p, t: functional_call(
            model, p, (t, model.init_cache(1, 16), 0), {"logits_at": 9},
            method="forward_cached",
        )
    ).lower(params, tokens).as_text(debug_info=True)
    for scope in ("gdn/conv", "gdn/chunk", "attn/gate", "moe/route",
                  "moe/experts", "moe/shared"):
        assert scope in prefill, scope
    kv = ServeEngine(model, num_slots=2, max_len=32).cache.kv
    decode = jax.jit(
        lambda p, t, c, pos: functional_call(
            model, p, (t, c, pos), method="forward_decode"
        )
    ).lower(params, tokens[:, :2].T, kv, jnp.zeros((2,), jnp.int32)).as_text(
        debug_info=True
    )
    for scope in ("gdn/conv", "gdn/update", "attn/gate", "attention", "mlp"):
        assert scope in decode, scope
