"""Self-speculative multi-token decoding (ISSUE 11).

The load-bearing invariants, pinned on the 8-device CPU mesh:

- **Greedy losslessness**: a ``speculate=K`` engine emits BIT-identical
  token streams to the ``speculate=0`` engine across slab/paged x
  chunked/persistent x occupancy — the verify block's row 0 IS the
  one-token forward (every op on the CPU f32 decode path is
  query-row-independent), and accepted rows match the greedy argmax by
  construction.  Sampled (temperature > 0) slots are forced to accept
  length 0, so their fold_in key schedule — and therefore their streams
  — are untouched.
- **Truncation law**: the device-side accepted count is
  ``e = max(1, min(1 + matches, first_eos, budget_left, room_left))``,
  so any finish condition lands exactly on a block's LAST emitted token
  and the host walk never has to split a block (pinned directly against
  ``_make_spec_decode_body`` with a deterministic chain-model stub).
- **KV safety under variable advance**: rejected-lane writes land
  beyond the live depth (overwritten before any accepted token can see
  them) or are DROPPED past the slot's row span — never clamped onto
  the last row, never wrapped into a neighbor slot
  (``scatter_slot_tokens`` / ``paged_scatter_tokens``).
- **Sync discipline**: speculation multiplies tokens per sync; it never
  adds one.  ``host_syncs == ring_drains`` in persistent mode, and the
  draft-economy counters obey ``accepted + rejected_lanes == proposed``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import torchdistx_tpu as tdx
from torchdistx_tpu.generation import (
    _make_decode_body,
    _make_slot_sampler,
    _make_spec_decode_body,
)
from torchdistx_tpu.models import GPT2, Llama
from torchdistx_tpu.serve import ServeEngine
from torchdistx_tpu.serve.kv_cache import merge_heads

_ULP = 3e-7  # ~2 f32 ulps at unit scale (test_decode_attention.py)


def _llama():
    tdx.manual_seed(0)
    return Llama.from_name("tiny", n_kv_heads=2, max_seq_len=64)


def _llama_tp():
    tdx.manual_seed(0)
    return Llama.from_name("tiny", max_seq_len=64)


def _gpt2():
    tdx.manual_seed(11)
    return GPT2.from_name("tiny")


def _tp_mesh(tp):
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:tp]), ("tp",))


def _cyclic_prompts():
    """Prompts whose tiny-Llama greedy continuations enter short cycles
    within ~10 tokens — the repetition self-speculation feeds on (the
    vLLM prompt-lookup workload, in miniature)."""
    return [
        np.array([3, 1, 2, 3, 1, 2, 3], np.int32),
        np.array([9, 9, 9, 9], np.int32),
        np.array([5, 7, 5, 7, 5], np.int32),
    ]


def _run(build, max_new=24, temps=None, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 64)
    engine = ServeEngine(build(), **kw)
    reqs = [
        {"prompt": p, "max_new_tokens": max_new} for p in _cyclic_prompts()
    ]
    if temps:
        for r, t in zip(reqs, temps):
            r["temperature"] = t
            r["seed"] = 7
    results = engine.run(reqs)
    return [list(map(int, r.tokens)) for r in results], engine


# --------------------------------------------------------------------------
# the truncation law, pinned directly against the device body
# --------------------------------------------------------------------------


class _ChainModel:
    """Deterministic ``forward_decode`` stub: next token after ``t`` is
    ``(t + 1) % vocab``, emitted as one-hot logits.  The KV pytree is
    passed through untouched — the stub isolates the body's draft/
    verify/truncate arithmetic from any real attention."""

    def __init__(self, vocab):
        self.vocab = vocab

    def forward_decode(self, tokens, cache, positions, page_tables=None):
        nxt = (tokens + 1) % self.vocab
        return jax.nn.one_hot(nxt, self.vocab, dtype=jnp.float32) * 10.0, cache


class TestSpecBodyTruncationLaw:
    V, MAX_LEN, K = 8, 32, 4

    def _step(self, eos=None):
        return _make_spec_decode_body(
            _ChainModel(self.V),
            _make_slot_sampler(jnp.int32, None, None),
            eos_token=eos,
            max_len=self.MAX_LEN,
            speculate=self.K,
            ngram=2,
        )

    def _carry(self, pos, stp=0, tok=None):
        # history = the 0..V-1 chain repeated up to (excluding) pos, so
        # the trailing bigram always has an earlier occurrence and the
        # drafts are exactly the true continuation
        hist = jnp.zeros((1, self.MAX_LEN), jnp.int32)
        hist = hist.at[0, :pos].set(jnp.arange(pos, dtype=jnp.int32) % self.V)
        if tok is None:
            tok = pos % self.V
        return (
            [],  # kv: the stub passes it through
            jnp.asarray([tok], jnp.int32),
            jnp.asarray([pos], jnp.int32),
            jnp.asarray([stp], jnp.int32),
            jnp.asarray([False]),
            hist,
        )

    def _apply(self, step, carry, budget=100, temp=0.0):
        return step(
            {},
            jnp.asarray([temp], jnp.float32),
            jnp.asarray([3], jnp.int32),
            jnp.asarray([budget], jnp.int32),
            (),
            carry,
        )

    def test_full_accept_emits_k_plus_one(self):
        (kv, tok, pos, stp, fin, hist), y, cnt = self._apply(
            self._step(), self._carry(pos=11)
        )
        np.testing.assert_array_equal(np.asarray(y)[0], [4, 5, 6, 7, 0])
        assert int(cnt[0]) == self.K + 1
        assert int(tok[0]) == 0 and int(pos[0]) == 16 and int(stp[0]) == 5
        assert not bool(fin[0])
        # the accepted tokens landed in the history at their stream index
        np.testing.assert_array_equal(
            np.asarray(hist)[0, 12:16], [4, 5, 6, 7]
        )

    def test_eos_inside_accepted_block_truncates(self):
        # continuation from 3 is 4,5,6,7,0 — eos=6 sits at block index 3
        (kv, tok, pos, stp, fin, hist), y, cnt = self._apply(
            self._step(eos=6), self._carry(pos=11)
        )
        assert int(cnt[0]) == 3  # 4, 5, then the EOS — nothing after
        assert int(tok[0]) == 6 and bool(fin[0])
        assert int(pos[0]) == 14 and int(stp[0]) == 3
        # rejected-lane history rows were never written
        np.testing.assert_array_equal(np.asarray(hist)[0, 15:17], [0, 0])

    def test_budget_exhausted_mid_block_truncates(self):
        (kv, tok, pos, stp, fin, hist), y, cnt = self._apply(
            self._step(), self._carry(pos=11, stp=0), budget=2
        )
        assert int(cnt[0]) == 2 and int(tok[0]) == 5
        assert bool(fin[0]) and int(stp[0]) == 2

    def test_cache_room_clamps_the_block(self):
        (kv, tok, pos, stp, fin, hist), y, cnt = self._apply(
            self._step(), self._carry(pos=self.MAX_LEN - 2)
        )
        assert int(cnt[0]) == 2  # only 2 rows of cache left
        assert bool(fin[0])  # slot is full: frozen from here on

    def test_no_ngram_match_falls_back_to_one_token(self):
        # two tokens of history cannot contain an EARLIER bigram match
        carry = self._carry(pos=1, tok=9 % self.V)
        (kv, tok, pos, stp, fin, hist), y, cnt = self._apply(
            self._step(), carry
        )
        assert int(cnt[0]) == 1 and int(pos[0]) == 2
        assert int(tok[0]) == (9 + 1) % self.V

    def test_sampled_row_reduces_to_nonspec_body(self):
        # temperature > 0 forces accept length 0; the one emitted token
        # and the carry advance must equal _make_decode_body's exactly
        # (same sampler, same fold_in(seed, stp) key)
        ref_step = _make_decode_body(
            _ChainModel(self.V),
            _make_slot_sampler(jnp.int32, None, None),
            eos_token=None,
            max_len=self.MAX_LEN,
        )
        kv, tok, pos, stp, fin, hist = self._carry(pos=11)
        temps = jnp.asarray([1.3], jnp.float32)
        seeds = jnp.asarray([3], jnp.int32)
        budgets = jnp.asarray([100], jnp.int32)
        _, rtok, rpos, rstp, rfin = ref_step(
            {}, temps, seeds, budgets, (), (kv, tok, pos, stp, fin)
        )
        (_, stok, spos, sstp, sfin, _), y, cnt = self._apply(
            self._step(), (kv, tok, pos, stp, fin, hist), temp=1.3
        )
        assert int(cnt[0]) == 1
        assert int(stok[0]) == int(rtok[0]) == int(np.asarray(y)[0, 0])
        assert int(spos[0]) == int(rpos[0])
        assert int(sstp[0]) == int(rstp[0])


# --------------------------------------------------------------------------
# multi-token KV scatter: drop semantics, never clamp, never wrap
# --------------------------------------------------------------------------


class TestMultiTokenScatter:
    def test_slab_scatter_drops_overflow_rows(self):
        from torchdistx_tpu.serve.kv_cache import scatter_slot_tokens

        rs = np.random.RandomState(0)
        cache = jnp.zeros((2, 8, 2 * 4), jnp.float32)  # stored: (B, L, H*D)
        x = jnp.asarray(rs.randn(2, 4, 2, 4), jnp.float32)
        out = np.asarray(
            scatter_slot_tokens(cache, x, jnp.asarray([6, 1], jnp.int32))
        )
        x = x.reshape(2, 4, 8)  # the rows as they land: head tail merged
        # slot 0 at pos 6: rows 6, 7 written; rows 8, 9 DROPPED — not
        # clamped onto row 7, not wrapped into slot 1's row 0/1
        np.testing.assert_array_equal(out[0, 6], np.asarray(x)[0, 0])
        np.testing.assert_array_equal(out[0, 7], np.asarray(x)[0, 1])
        np.testing.assert_array_equal(out[0, :6], 0)
        np.testing.assert_array_equal(out[1, 1:5], np.asarray(x)[1])
        np.testing.assert_array_equal(out[1, 0], 0)
        np.testing.assert_array_equal(out[1, 5:], 0)

    def test_paged_scatter_routes_through_tables_and_drops(self):
        from torchdistx_tpu.serve.kv_cache import paged_scatter_tokens

        rs = np.random.RandomState(1)
        ps, npages = 4, 6
        pool = jnp.zeros((npages, ps, 2 * 4), jnp.float32)  # stored layout
        x = jnp.asarray(rs.randn(2, 3, 2, 4), jnp.float32)
        # slot 0: pages [2, 5], logical span 8 rows; slot 1: pages [4, 1]
        tables = jnp.asarray([[2, 5], [4, 1]], jnp.int32)
        out = np.asarray(
            paged_scatter_tokens(
                pool, x, tables, jnp.asarray([3, 6], jnp.int32), ps
            )
        )
        xx = np.asarray(x).reshape(2, 3, 8)  # head tail merged on the rows
        # slot 0 offsets 3,4,5 -> page 2 row 3, page 5 rows 0,1
        np.testing.assert_array_equal(out[2, 3], xx[0, 0])
        np.testing.assert_array_equal(out[5, 0], xx[0, 1])
        np.testing.assert_array_equal(out[5, 1], xx[0, 2])
        # slot 1 offsets 6,7 -> page 1 rows 2,3; offset 8 is past the
        # table span: DROPPED, not clamped into the last page
        np.testing.assert_array_equal(out[1, 2], xx[1, 0])
        np.testing.assert_array_equal(out[1, 3], xx[1, 1])
        np.testing.assert_array_equal(out[4], 0)  # untouched page
        np.testing.assert_array_equal(out[0], 0)
        np.testing.assert_array_equal(out[3], 0)


# --------------------------------------------------------------------------
# the (B, S) verify attention: jnp block path and the pallas kernels
# --------------------------------------------------------------------------


class TestVerifyBlockAttention:
    def _case(self, rs, b, s, hq, hkv, d, max_seq, positions):
        q = jnp.asarray(rs.randn(b, s, hq, d), jnp.float32)
        ck = jnp.asarray(rs.randn(b, max_seq, hkv, d), jnp.float32)
        cv = jnp.asarray(rs.randn(b, max_seq, hkv, d), jnp.float32)
        return q, ck, cv, jnp.asarray(positions, jnp.int32)

    def test_block_row_i_matches_single_token_at_depth(self):
        # row i of the (B, S) block attention equals the (B, 1)
        # attention at depth pos + i on the same cache — to f32 ulp,
        # not bitwise: every op in the chain is query-row-independent
        # mathematically, but XLA lowers the S=1 and S=3 contractions
        # differently (matvec vs batched matmul accumulation order).
        # The engine-level identity tests pin the thing that must be
        # EXACT — the emitted token streams.
        from torchdistx_tpu.ops.attention import (
            _slot_attend,
            _slot_attend_block,
        )

        rs = np.random.RandomState(2)
        b, s, hq, hkv, d, max_seq = 2, 3, 4, 2, 8, 16
        q, ck, cv, pos = self._case(rs, b, s, hq, hkv, d, max_seq, [5, 9])
        blk = _slot_attend_block(q, ck, cv, pos, 1.0 / np.sqrt(d))
        for i in range(s):
            one = _slot_attend(
                q[:, i : i + 1], ck, cv, pos + i, 1.0 / np.sqrt(d), None
            )
            np.testing.assert_allclose(
                np.asarray(blk)[:, i],
                np.asarray(one)[:, 0],
                rtol=_ULP,
                atol=_ULP,
            )

    @pytest.mark.parametrize("hq,hkv,s", [(4, 2, 2), (4, 4, 3), (8, 2, 5)])
    def test_block_kernel_matches_jnp_path(self, hq, hkv, s):
        from torchdistx_tpu.ops.attention import _slot_attend_block
        from torchdistx_tpu.ops.decode_attention import (
            decode_attention_block,
        )

        rs = np.random.RandomState(hq * 100 + hkv * 10 + s)
        b, d, max_seq = 2, 8, 64
        q, ck, cv, pos = self._case(
            rs, b, s, hq, hkv, d, max_seq, [37, max_seq - s]
        )
        ref = _slot_attend_block(q, ck, cv, pos, 1.0 / np.sqrt(d))
        for block_k in (16, 512):  # multi-block online softmax AND 1-block
            out = decode_attention_block(
                q, merge_heads(ck), merge_heads(cv), pos, block_k=block_k,
                interpret=True,
            )
            np.testing.assert_allclose(out, ref, rtol=_ULP, atol=_ULP)

    def test_block_kernel_position_zero(self):
        from torchdistx_tpu.ops.attention import _slot_attend_block
        from torchdistx_tpu.ops.decode_attention import (
            decode_attention_block,
        )

        rs = np.random.RandomState(5)
        q, ck, cv, pos = self._case(rs, 2, 3, 4, 2, 8, 16, [0, 13])
        ref = _slot_attend_block(q, ck, cv, pos, 1.0 / np.sqrt(8))
        out = decode_attention_block(
            q, merge_heads(ck), merge_heads(cv), pos, interpret=True
        )
        np.testing.assert_allclose(out, ref, rtol=_ULP, atol=_ULP)

    @pytest.mark.parametrize("s", [2, 4])
    def test_paged_block_kernel_matches_slab_reference(self, s):
        from torchdistx_tpu.ops.attention import _slot_attend_block
        from torchdistx_tpu.ops.decode_attention import (
            paged_decode_attention_block,
        )

        rs = np.random.RandomState(s)
        b, hq, hkv, d, ps, pp = 2, 4, 2, 8, 8, 4
        q = jnp.asarray(rs.randn(b, s, hq, d), jnp.float32)
        pool_k = jnp.asarray(rs.randn(pp * b, ps, hkv, d), jnp.float32)
        pool_v = jnp.asarray(rs.randn(pp * b, ps, hkv, d), jnp.float32)
        tables = jnp.asarray([[0, 2, 4, 6], [1, 3, 5, 7]], jnp.int32)
        pos = jnp.asarray([13, pp * ps - s], jnp.int32)
        # slab reference: gather each slot's logical rows from the pools
        gather = lambda pool: pool.reshape(-1, hkv, d)[
            (tables[:, :, None] * ps + jnp.arange(ps)[None, None, :])
            .reshape(b, pp * ps)
        ]
        ref = _slot_attend_block(
            q, gather(pool_k), gather(pool_v), pos, 1.0 / np.sqrt(d)
        )
        out = paged_decode_attention_block(
            q, merge_heads(pool_k), merge_heads(pool_v), tables, pos,
            interpret=True,
        )
        np.testing.assert_allclose(out, ref, rtol=_ULP, atol=_ULP)


# --------------------------------------------------------------------------
# engine-level greedy losslessness
# --------------------------------------------------------------------------


class TestSpecEngineIdentity:
    def test_chunked_slab_identity_fast(self):
        base, eng0 = _run(_llama, decode_mode="chunked")
        spec, eng = _run(_llama, decode_mode="chunked", speculate=2)
        assert spec == base
        c = eng.metrics.counters
        assert c["draft_tokens_proposed"] > 0
        assert c["draft_tokens_accepted"] > 0
        assert c["host_syncs"] <= eng0.metrics.counters["host_syncs"]

    def test_persistent_slab_identity_fast(self):
        base, eng0 = _run(_llama, decode_mode="persistent")
        spec, eng = _run(_llama, decode_mode="persistent", speculate=2)
        assert spec == base
        c = eng.metrics.counters
        assert c["draft_tokens_accepted"] > 0
        # speculation multiplies tokens per sync — it never adds one
        assert c["host_syncs"] == eng0.metrics.counters["host_syncs"]
        assert c["host_syncs"] == c["ring_drains"]

    def test_persistent_fewer_loop_iterations(self):
        _, eng0 = _run(_llama, decode_mode="persistent")
        _, eng = _run(_llama, decode_mode="persistent", speculate=4)
        assert (
            eng.metrics.counters["loop_iterations"]
            < eng0.metrics.counters["loop_iterations"]
        )
        atpi = eng.metrics.to_json()["derived"][
            "accepted_tokens_per_iteration"
        ]
        assert atpi is not None and atpi > 1.0

    def test_paged_identity_fast(self):
        base, _ = _run(_llama, decode_mode="persistent")
        spec, _ = _run(
            _llama, decode_mode="persistent", speculate=2, page_size=8
        )
        assert spec == base

    def test_gpt2_identity_fast(self):
        base, _ = _run(_gpt2, decode_mode="persistent")
        spec, eng = _run(_gpt2, decode_mode="persistent", speculate=2)
        assert spec == base
        assert eng.metrics.counters["draft_tokens_proposed"] > 0

    @pytest.mark.slow
    @pytest.mark.parametrize("mode", ["chunked", "persistent"])
    @pytest.mark.parametrize("page_size", [None, 8])
    @pytest.mark.parametrize("speculate", [2, 4])
    @pytest.mark.parametrize("num_slots", [2, 4])
    def test_identity_grid(self, mode, page_size, speculate, num_slots):
        kw = dict(decode_mode=mode, num_slots=num_slots)
        if page_size is not None:
            kw["page_size"] = page_size
        if mode == "chunked":
            kw["decode_chunk"] = 2
        base, _ = _run(_llama, **kw)
        spec, _ = _run(_llama, speculate=speculate, **kw)
        assert spec == base

    def test_sampled_streams_identical_at_accept_zero(self):
        temps = [0.9, 0.0, 1.4]
        for mode in ("chunked", "persistent"):
            base, _ = _run(_llama, decode_mode=mode, temps=temps)
            spec, eng = _run(
                _llama, decode_mode=mode, speculate=2, temps=temps
            )
            assert spec == base
            # the greedy slot still speculates; the sampled ones add
            # proposals (every live iteration proposes) but no accepts
            # beyond what the greedy rows earn
            assert eng.metrics.counters["draft_tokens_proposed"] > 0

    def test_eos_stop_identical(self):
        def go(speculate, eos):
            engine = ServeEngine(
                _llama(),
                num_slots=2,
                max_len=64,
                eos_token=eos,
                decode_mode="persistent",
                speculate=speculate,
            )
            res = engine.run(
                [
                    {"prompt": p, "max_new_tokens": 24}
                    for p in _cyclic_prompts()
                ]
            )
            return [(list(map(int, r.tokens)), r.finish_reason) for r in res]

        # EOS = a token the model really emits mid-stream (which one is
        # the compiler's business): the 6th token of the first free-run
        # stream, so at least that request stops early
        free, _ = go(0, None)[0]
        eos = free[5]
        base, spec = go(0, eos), go(4, eos)
        assert spec == base
        assert any(reason == "stop" for _, reason in base)


# --------------------------------------------------------------------------
# rejected-lane KV virginity
# --------------------------------------------------------------------------


class TestRejectedLaneKV:
    def test_live_rows_match_nonspec(self):
        # rejected-lane writes land beyond the live depth and are
        # overwritten before any accepted token can attend to them —
        # so every REAL row of a finished slot holds the SAME token's
        # K/V projection as the non-speculative engine's, to f32 ulp
        # (the projections run through a (B, K+1) matmul vs a (B, 1)
        # one, so XLA's accumulation order differs; a rejected-lane
        # row surviving would differ at O(1), not O(ulp)).  The
        # stream's last token is never written back (the slot finishes
        # instead), so the real rows are prompt + gen[:-1] == depth-1
        # of them; the row AT depth-1 is each engine's frozen-slot
        # garbage row (non-spec keeps writing it at the frozen pos
        # while other slots decode) and legitimately differs.
        prompts = _cyclic_prompts()
        caches = {}
        for K in (0, 4):
            engine = ServeEngine(
                _llama(),
                num_slots=len(prompts),
                max_len=64,
                decode_mode="persistent",
                speculate=K,
            )
            engine.run([{"prompt": p, "max_new_tokens": 12} for p in prompts])
            caches[K] = engine
        for slot, p in enumerate(prompts):
            real = p.size + 12 - 1
            for (k0, v0), (k1, v1) in zip(
                caches[0].cache.kv, caches[4].cache.kv
            ):
                np.testing.assert_allclose(
                    np.asarray(k0)[slot, :real],
                    np.asarray(k1)[slot, :real],
                    rtol=_ULP,
                    atol=_ULP,
                )
                np.testing.assert_allclose(
                    np.asarray(v0)[slot, :real],
                    np.asarray(v1)[slot, :real],
                    rtol=_ULP,
                    atol=_ULP,
                )

    def test_overflow_never_corrupts_neighbor_slot(self):
        # slot 0 decodes all the way to max_len with K=4 drafts — the
        # final blocks' rejected lanes index past the slab row span and
        # must be DROPPED.  A clamp or flat-index wrap would land them
        # in slot 1's live rows, so slot 1's long-running stream is the
        # corruption detector: both streams must stay bit-identical to
        # the non-speculative engine's.
        reqs = [
            {"prompt": np.array([9, 9, 9, 9], np.int32),
             "max_new_tokens": 60},
            {"prompt": np.array([3, 1, 2, 3, 1, 2, 3], np.int32),
             "max_new_tokens": 40},
        ]

        def go(K):
            engine = ServeEngine(
                _llama(),
                num_slots=2,
                max_len=64,
                decode_mode="persistent",
                speculate=K,
            )
            res = engine.run([dict(r) for r in reqs])
            return [
                (list(map(int, r.tokens)), r.finish_reason) for r in res
            ]

        base, spec = go(0), go(4)
        assert spec == base
        assert len(spec[0][0]) == 60  # slot 0 really hit the boundary


# --------------------------------------------------------------------------
# counters, gauges, config plumbing
# --------------------------------------------------------------------------


class TestSpecMetrics:
    def test_counter_identity_and_derived(self):
        _, eng = _run(_llama, decode_mode="persistent", speculate=2)
        c = eng.metrics.counters
        assert (
            c["draft_tokens_accepted"] + c["spec_rejected_lane_steps"]
            == c["draft_tokens_proposed"]
        )
        j = eng.metrics.to_json()
        assert j["gauges"]["speculate"] == 2
        prop, acc = c["draft_tokens_proposed"], c["draft_tokens_accepted"]
        assert j["derived"]["accept_rate"] == acc / prop
        assert (
            j["derived"]["accepted_tokens_per_iteration"]
            == 1.0 + acc * 2 / prop
        )

    def test_nonspec_engine_reports_zero_and_no_gauge(self):
        _, eng = _run(_llama, decode_mode="persistent")
        j = eng.metrics.to_json()
        assert j["counters"]["draft_tokens_proposed"] == 0
        assert "speculate" not in j["gauges"]
        assert j["derived"]["accept_rate"] is None
        assert j["derived"]["accepted_tokens_per_iteration"] is None

    def test_prometheus_collector_exports_spec_family(self):
        from torchdistx_tpu.obs.metrics import (
            MetricsRegistry,
            parse_prometheus,
        )

        _, eng = _run(_llama, decode_mode="persistent", speculate=2)
        reg = MetricsRegistry()
        reg.register_collector(eng.metrics.collector(), obj=eng.metrics)
        parsed = parse_prometheus(reg.render())
        samples = parsed["samples"]
        c = eng.metrics.counters
        assert (
            samples[("tdx_serve_draft_tokens_proposed_total", ())]
            == c["draft_tokens_proposed"]
        )
        assert (
            samples[("tdx_serve_draft_tokens_accepted_total", ())]
            == c["draft_tokens_accepted"]
        )
        assert samples[("tdx_serve_speculate", ())] == 2
        assert parsed["types"]["tdx_serve_draft_tokens_proposed_total"] == (
            "counter"
        )

    def test_reset_metrics_preserves_spec_gauges(self):
        # the PR 6 regression, extended: a bench per-phase reset must
        # keep the engine-geometry gauges — speculate included
        _, eng = _run(
            _llama, decode_mode="persistent", speculate=2, ring_capacity=32
        )
        fresh = eng.reset_metrics()
        assert fresh is eng.metrics
        j = fresh.to_json()
        assert j["gauges"]["speculate"] == 2
        assert j["gauges"]["ring_capacity"] == 32
        assert j["counters"]["draft_tokens_proposed"] == 0

    def test_config_validation(self):
        with pytest.raises(ValueError, match="speculate must be"):
            ServeEngine(_llama(), num_slots=1, max_len=32, speculate=-1)
        with pytest.raises(ValueError, match="spec_ngram"):
            ServeEngine(
                _llama(), num_slots=1, max_len=32, speculate=2, spec_ngram=0
            )
        with pytest.raises(ValueError, match="persistent_stream"):
            ServeEngine(
                _llama(),
                num_slots=1,
                max_len=32,
                decode_mode="persistent",
                persistent_stream=True,
                speculate=2,
            )


# --------------------------------------------------------------------------
# tensor-parallel serving with speculation
# --------------------------------------------------------------------------


class TestSpecTP:
    def test_tp2_identity_and_collective_closed_form(self):
        from torchdistx_tpu.obs.comm import comm_audit

        prompts = _cyclic_prompts()

        def go(speculate, mesh=None):
            engine = ServeEngine(
                _llama_tp(),
                num_slots=2,
                max_len=64,
                prefill_buckets=(16,),
                decode_mode="persistent",
                speculate=speculate,
                mesh=mesh,
            )
            res = engine.run(
                [{"prompt": p, "max_new_tokens": 16} for p in prompts]
            )
            return [list(map(int, r.tokens)) for r in res], engine

        base, _ = go(0)
        with comm_audit() as prof:
            spec, engine = go(2, mesh=_tp_mesh(2))
        assert spec == base
        c = engine.metrics.counters
        model_cfg = engine.model.cfg
        nl, dim = model_cfg.n_layers, model_cfg.dim
        assert prof.ops("all_reduce", "tp") == 2 * nl * (
            c["prefill_calls"] + c["decode_steps"]
        )
        # every spec decode step verifies num_slots x (K + 1) query rows
        expected_payload = (
            2 * nl * 4 * dim
            * (
                c["tokens_prefilled"]
                + c["decode_steps"] * engine.num_slots * 3
            )
        )
        assert prof.payload_bytes("all_reduce", "tp") == expected_payload

    @pytest.mark.slow
    @pytest.mark.parametrize("mode", ["chunked", "persistent"])
    @pytest.mark.parametrize("page_size", [None, 8])
    def test_tp2_identity_grid(self, mode, page_size):
        prompts = _cyclic_prompts()

        def go(speculate, mesh):
            kw = dict(
                num_slots=2,
                max_len=64,
                prefill_buckets=(16,),
                decode_mode=mode,
                speculate=speculate,
            )
            if page_size is not None:
                kw["page_size"] = page_size
            engine = ServeEngine(_llama_tp(), mesh=mesh, **kw)
            res = engine.run(
                [{"prompt": p, "max_new_tokens": 16} for p in prompts]
            )
            return [list(map(int, r.tokens)) for r in res]

        assert go(2, _tp_mesh(2)) == go(0, None)
