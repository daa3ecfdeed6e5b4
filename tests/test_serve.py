"""Continuous-batching serving engine (torchdistx_tpu.serve).

The load-bearing invariants, pinned on the 8-device CPU mesh:

- **Exactness**: a greedy request served through the slot cache is
  bit-identical to ``generation.generate`` on that prompt alone — padding,
  slot reuse, and batch-mates change nothing.
- **Fused decode exactness**: a ``decode_chunk=K`` engine (K decode steps
  per dispatch in one on-device scan, one host sync per K tokens) emits
  BIT-identical token streams to the K=1 engine, greedy and sampled,
  full and partial slot occupancy — and a slot finishing at in-chunk
  step ``j`` contributes nothing after ``j``: its tokens stop, its KV
  rows freeze, and ``masked_slot_steps`` accounts exactly the
  ``K - 1 - j`` wasted slot-steps.
- **Dispatch discipline**: a full mixed-length continuous-batching run —
  including a late request admitted into a freed (dirty) slot — compiles
  exactly two programs (one prefill bucket + one decode scan per
  ``decode_chunk`` value).
- **Paged prefix-cache exactness**: a ``page_size=N`` engine — shared
  prefixes served from cached pages, suffix-only prefill, page-table
  decode — emits BIT-identical token streams to the contiguous
  (cache-off) engine across K x occupancy x shared/disjoint prefix
  mixes, cold AND warm (tests/test_prefix_cache.py covers the allocator
  and index units).
- **Deadlines**: expiry returns a partial result flagged ``truncated``.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchdistx_tpu as tdx
from torchdistx_tpu.generation import generate
from torchdistx_tpu.models import GPT2, Llama
from torchdistx_tpu.serve import Request, Scheduler, ServeEngine, SlotKVCache
from torchdistx_tpu.serve.kv_cache import merge_heads, split_heads
from torchdistx_tpu.serve.metrics import Histogram, ServeMetrics


def _llama():
    tdx.manual_seed(0)
    return Llama.from_name("tiny", n_kv_heads=2, max_seq_len=64)


def _gpt2():
    tdx.manual_seed(11)
    return GPT2.from_name("tiny")


def _prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 256, (n,)).astype(np.int32) for n in lengths]


class TestSlotDecodeParity:
    """forward_decode (per-row positions) row-for-row equals
    forward_cached (scalar position) — the primitive the engine's
    bit-identity rests on."""

    def test_slot_attention_matches_scalar_cached_attention(self):
        from torchdistx_tpu.ops.attention import (
            cached_attention,
            slot_cached_attention,
        )

        rs = np.random.RandomState(3)
        b, hq, hkv, d, max_seq = 3, 4, 2, 8, 16
        q = jnp.asarray(rs.randn(b, 1, hq, d), jnp.float32)
        k = jnp.asarray(rs.randn(b, 1, hkv, d), jnp.float32)
        v = jnp.asarray(rs.randn(b, 1, hkv, d), jnp.float32)
        cache = (
            jnp.asarray(rs.randn(b, max_seq, hkv, d), jnp.float32),
            jnp.asarray(rs.randn(b, max_seq, hkv, d), jnp.float32),
        )
        positions = np.array([2, 9, 5], np.int32)
        # the slot primitive takes and returns the engine's stored
        # layout (head tail merged); the scalar one the model's
        out, stored = slot_cached_attention(
            q, k, v, tuple(merge_heads(c) for c in cache),
            jnp.asarray(positions),
        )
        ck, cv = (split_heads(c, hkv) for c in stored)
        for row, p in enumerate(positions):
            r = slice(row, row + 1)
            ref, (rk, rv) = cached_attention(
                q[r], k[r], v[r],
                (cache[0][r], cache[1][r]), int(p), use_flash=False,
            )
            np.testing.assert_array_equal(np.asarray(out[r]), np.asarray(ref))
            np.testing.assert_array_equal(np.asarray(ck[r]), np.asarray(rk))
            np.testing.assert_array_equal(np.asarray(cv[r]), np.asarray(rv))

    def test_model_forward_decode_matches_forward_cached(self):
        for model in (_llama(), _gpt2()):
            rs = np.random.RandomState(4)
            toks = jnp.asarray(rs.randint(0, 256, (3, 1)), jnp.int32)
            positions = np.array([1, 7, 4], np.int32)
            caches = [model.init_cache(1, 16) for _ in range(3)]
            # place a little real content at each row's depth
            seeded = []
            for row, p in enumerate(positions):
                pre = jnp.asarray(
                    rs.randint(0, 256, (1, int(p))), jnp.int32
                )
                _, c = model.forward_cached(pre, caches[row], 0)
                seeded.append(c)
            big = [
                (
                    jnp.concatenate([c[i][0] for c in seeded]),
                    jnp.concatenate([c[i][1] for c in seeded]),
                )
                for i in range(len(seeded[0]))
            ]
            logits, _ = model.forward_decode(
                toks,
                [tuple(merge_heads(c) for c in pair) for pair in big],
                jnp.asarray(positions),
            )
            for row, p in enumerate(positions):
                r = slice(row, row + 1)
                ref, _ = model.forward_cached(toks[r], seeded[row], int(p))
                np.testing.assert_array_equal(
                    np.asarray(logits[r]), np.asarray(ref)
                )


class TestServeExactness:
    def test_greedy_bit_identical_to_sequential_generate(self):
        model = _llama()
        engine = ServeEngine(
            model, num_slots=3, max_len=64, prefill_buckets=(16,)
        )
        prompts = _prompts(0, (6, 11, 9, 4, 13))
        results = engine.run(
            [{"prompt": p, "max_new_tokens": 8} for p in prompts]
        )
        for p, r in zip(prompts, results):
            assert r.finish_reason == "length" and not r.truncated
            ref = np.asarray(generate(model, jnp.asarray(p[None]), 8))[0]
            np.testing.assert_array_equal(
                np.concatenate([p, r.tokens]), ref
            )

    def test_greedy_row_unaffected_by_sampling_batchmate(self):
        model = _gpt2()
        prompts = _prompts(1, (5, 7))
        engine = ServeEngine(model, num_slots=2, max_len=32)
        greedy = engine.submit(prompts[0], max_new_tokens=6)
        engine.submit(
            prompts[1], max_new_tokens=6, temperature=1.0, seed=3
        )
        while engine.step():
            pass
        ref = np.asarray(generate(model, jnp.asarray(prompts[0][None]), 6))[0]
        np.testing.assert_array_equal(
            np.concatenate([prompts[0], greedy.result().tokens]), ref
        )

    def test_sampling_reproducible_per_seed(self):
        model = _gpt2()
        prompt = _prompts(2, (6,))[0]
        engine = ServeEngine(model, num_slots=2, max_len=32, top_k=50)

        def sample(seed):
            h = engine.submit(
                prompt, max_new_tokens=6, temperature=0.8, seed=seed
            )
            while not h.done():
                engine.step()
            return h.result().tokens

        a, b, c = sample(7), sample(7), sample(8)
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_eos_stops_with_stop_reason(self):
        model = _llama()
        prompt = _prompts(3, (5,))[0]
        first = np.asarray(generate(model, jnp.asarray(prompt[None]), 1))[
            0, -1
        ]
        engine = ServeEngine(
            model, num_slots=1, max_len=64, eos_token=int(first)
        )
        r = engine.run([{"prompt": prompt, "max_new_tokens": 8}])[0]
        assert r.finish_reason == "stop" and not r.truncated
        np.testing.assert_array_equal(r.tokens, [int(first)])


class TestContinuousBatching:
    def test_late_admit_into_freed_slot_no_recompile(self):
        """Mixed lengths, staggered finishes, a late submit landing in a
        freed (dirty) slot — and the jit cache holds exactly TWO programs
        throughout (one prefill bucket, one decode step)."""
        model = _llama()
        engine = ServeEngine(
            model, num_slots=2, max_len=64, prefill_buckets=(16,)
        )
        prompts = _prompts(5, (4, 9, 7))
        h0 = engine.submit(prompts[0], max_new_tokens=3)
        h1 = engine.submit(prompts[1], max_new_tokens=12)
        while not h0.done():
            engine.step()
        assert not h1.done()  # slot 1 still decoding
        warm = engine.num_compiled_programs()
        if warm is None:
            pytest.skip("jit cache introspection unavailable on this jax")
        assert warm == 2  # one prefill bucket + one decode step
        # late arrival: must reuse h0's freed slot while h1 keeps going
        h2 = engine.submit(prompts[2], max_new_tokens=6)
        while engine.step():
            pass
        assert engine.num_compiled_programs() == warm == 2
        for p, h, n in ((prompts[1], h1, 12), (prompts[2], h2, 6)):
            ref = np.asarray(generate(model, jnp.asarray(p[None]), n))[0]
            np.testing.assert_array_equal(
                np.concatenate([p, h.result().tokens]), ref
            )
        snap = engine.metrics.snapshot()
        assert snap["requests_completed"] == 3
        assert snap["tokens_generated"] == 3 + 12 + 6

    def test_queue_deeper_than_slots_drains_fcfs(self):
        model = _llama()
        engine = ServeEngine(
            model, num_slots=2, max_len=64, prefill_buckets=(16,)
        )
        prompts = _prompts(6, (3, 5, 7, 4, 6, 8))
        results = engine.run(
            [{"prompt": p, "max_new_tokens": 4} for p in prompts]
        )
        assert [r.rid for r in results] == sorted(r.rid for r in results)
        for p, r in zip(prompts, results):
            ref = np.asarray(generate(model, jnp.asarray(p[None]), 4))[0]
            np.testing.assert_array_equal(
                np.concatenate([p, r.tokens]), ref
            )
        assert engine.num_compiled_programs() in (2, None)

    def test_max_tokens_budget_defers_admission(self):
        model = _llama()
        engine = ServeEngine(
            model,
            num_slots=2,
            max_len=64,
            prefill_buckets=(16,),
            max_tokens_in_flight=20,
        )
        prompts = _prompts(7, (6, 6))
        engine.submit(prompts[0], max_new_tokens=8)  # cost 14
        h1 = engine.submit(prompts[1], max_new_tokens=8)  # would be 28 > 20
        engine.step()
        assert engine.scheduler.queue_depth == 1  # deferred, slot free
        while engine.step():
            pass
        assert h1.done()  # admitted after the first retired
        ref = np.asarray(generate(model, jnp.asarray(prompts[1][None]), 8))[0]
        np.testing.assert_array_equal(
            np.concatenate([prompts[1], h1.result().tokens]), ref
        )


class TestDeadlines:
    def test_running_deadline_returns_truncated_partial(self):
        model = _llama()
        engine = ServeEngine(
            model, num_slots=1, max_len=64, prefill_buckets=(16,)
        )
        prompt = _prompts(8, (5,))[0]
        h = engine.submit(prompt, max_new_tokens=40, deadline_s=0.2)
        engine.step()  # prefill + first decode: some tokens exist
        engine.step()
        time.sleep(0.25)
        engine.step()  # past deadline now
        r = h.result()
        assert r.finish_reason == "deadline" and r.truncated
        assert 0 < len(r.tokens) < 40
        # the partial prefix is still exact
        ref = np.asarray(
            generate(model, jnp.asarray(prompt[None]), len(r.tokens))
        )[0]
        np.testing.assert_array_equal(np.concatenate([prompt, r.tokens]), ref)
        assert engine.metrics.snapshot()["requests_truncated"] == 1

    def test_queued_deadline_expires_with_no_tokens(self):
        model = _llama()
        engine = ServeEngine(
            model, num_slots=1, max_len=64, prefill_buckets=(16,)
        )
        prompts = _prompts(9, (5, 6))
        engine.submit(prompts[0], max_new_tokens=30)
        h = engine.submit(prompts[1], max_new_tokens=4, deadline_s=0.0)
        engine.step()
        r = h.result()
        assert r.truncated and r.finish_reason == "deadline"
        assert r.tokens.size == 0


def _run_chunked(model, k_chunk, requests, *, num_slots=3, eos_token=None,
                 max_len=64, buckets=(16,), **engine_kw):
    engine = ServeEngine(
        model, num_slots=num_slots, max_len=max_len,
        prefill_buckets=buckets, eos_token=eos_token,
        decode_chunk=k_chunk, **engine_kw,
    )
    return engine, engine.run([dict(r) for r in requests])


class TestFusedDecode:
    """decode_chunk=K: K tokens per dispatch and per host sync, streams
    bit-identical to the K=1 engine.  The fast tests cover K=4 at both
    occupancies, greedy and sampled; the slow sweep runs the full
    K x occupancy x sampling grid (same code path, nightly)."""

    def _requests(self, lengths, temperature, n_new=8):
        return [
            {"prompt": p, "max_new_tokens": n_new,
             "temperature": temperature, "seed": i}
            for i, p in enumerate(_prompts(21, lengths))
        ]

    def _assert_identical(self, k_chunk, lengths, temperature):
        model = _llama()
        reqs = self._requests(lengths, temperature)
        _, base = _run_chunked(model, 1, reqs)
        engine, fused = _run_chunked(model, k_chunk, reqs)
        for a, b in zip(base, fused):
            assert a.finish_reason == b.finish_reason
            np.testing.assert_array_equal(a.tokens, b.tokens)
        return engine

    def test_k4_greedy_full_and_partial_occupancy(self):
        # full: 5 requests through 3 slots (churn + late admission at
        # chunk boundaries); partial: 1 request, 2 slots idle
        engine = self._assert_identical(4, (6, 11, 9, 4, 13), 0.0)
        snap = engine.metrics.snapshot()
        assert snap["decode_steps"] == 4 * snap["decode_dispatches"]
        # one sync per prefill + one per K-step dispatch, NOT per token
        assert snap["host_syncs"] == (
            snap["prefill_calls"] + snap["decode_dispatches"]
        )
        assert snap["syncs_per_token"] < 0.5  # vs ~1.1 at K=1
        self._assert_identical(4, (7,), 0.0)

    def test_k4_sampled_full_and_partial_occupancy(self):
        self._assert_identical(4, (6, 11, 9, 4, 13), 0.9)
        self._assert_identical(4, (7,), 0.9)

    def test_fused_decode_through_pallas_kernel_path(self):
        """use_flash=True routes the in-scan attention through the
        interpret-mode pallas decode kernel on CPU: fused-vs-sequential
        stays BIT-identical because both engines share the kernel."""
        tdx.manual_seed(0)
        model = Llama.from_name(
            "tiny", n_kv_heads=2, max_seq_len=64, use_flash=True
        )
        reqs = self._requests((6, 9), 0.0, n_new=6)
        _, base = _run_chunked(model, 1, reqs, num_slots=2)
        _, fused = _run_chunked(model, 4, reqs, num_slots=2)
        for a, b in zip(base, fused):
            np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_program_count_one_decode_per_k(self):
        model = _llama()
        engine, _ = _run_chunked(model, 4, self._requests((6, 9), 0.0))
        warm = engine.num_compiled_programs()
        if warm is None:
            pytest.skip("jit cache introspection unavailable on this jax")
        assert warm == 2  # one prefill bucket + ONE K=4 decode scan
        # more traffic never compiles more
        engine.run([dict(r) for r in self._requests((5, 12, 8), 0.0)])
        assert engine.num_compiled_programs() == 2

    @pytest.mark.slow
    @pytest.mark.parametrize("k_chunk", [1, 4, 8])
    @pytest.mark.parametrize("lengths", [(6, 11, 9, 4, 13), (7,)])
    @pytest.mark.parametrize("temperature", [0.0, 0.9])
    def test_full_grid_bit_identical(self, k_chunk, lengths, temperature):
        self._assert_identical(k_chunk, lengths, temperature)


class TestPagedPrefixSharing:
    """page_size=N engine vs the contiguous cache-off engine: BIT
    identical streams, cold and warm — shared prefixes, disjoint
    prompts, slot churn, greedy and sampled rows.  The fast tests cover
    K=4 at both occupancies plus a warm pass; the slow sweep runs the
    full K x occupancy x prefix-mix grid (same code path, nightly)."""

    # prefix mixes: lengths with None meaning "prepend the shared
    # 20-token system prefix" (page-aligned hits at page_size=8 come
    # from its first 16 tokens)
    SHARED = (("s", 5), ("s", 9), (None, 3), ("s", 12), (None, 7))
    DISJOINT = ((None, 6), (None, 11), (None, 9), (None, 4), (None, 13))

    def _requests(self, mix, temperature, n_new=8):
        rs = np.random.RandomState(17)
        shared = rs.randint(0, 256, (20,)).astype(np.int32)
        reqs = []
        for i, (pfx, n) in enumerate(mix):
            tail = rs.randint(0, 256, (n,)).astype(np.int32)
            prompt = np.concatenate([shared, tail]) if pfx else tail
            reqs.append(
                {"prompt": prompt, "max_new_tokens": n_new,
                 "temperature": temperature, "seed": i}
            )
        return reqs

    def _assert_paged_identical(self, k_chunk, mix, temperature,
                                num_slots=3):
        model = _llama()
        reqs = self._requests(mix, temperature)
        _, base = _run_chunked(
            model, k_chunk, reqs, num_slots=num_slots, buckets=(16, 32)
        )
        paged = ServeEngine(
            model, num_slots=num_slots, max_len=64,
            prefill_buckets=(16, 32), decode_chunk=k_chunk, page_size=8,
        )
        cold = paged.run([dict(r) for r in reqs])
        warm = paged.run([dict(r) for r in reqs])  # index now populated
        for a, b, c in zip(base, cold, warm):
            assert a.finish_reason == b.finish_reason == c.finish_reason
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_array_equal(a.tokens, c.tokens)
        return paged

    def test_k4_greedy_shared_prefix_cold_and_warm(self):
        engine = self._assert_paged_identical(4, self.SHARED, 0.0)
        snap = engine.metrics.snapshot()
        assert snap["prefix_hit_tokens"] > 0  # sharing actually happened
        # partial occupancy: one request, slots idle
        self._assert_paged_identical(4, ((None, 7),), 0.0)

    def test_k4_sampled_shared_prefix(self):
        self._assert_paged_identical(4, self.SHARED, 0.9)

    def test_k1_disjoint_prompts(self):
        engine = self._assert_paged_identical(1, self.DISJOINT, 0.0)
        # disjoint tails shorter than a page: no false hits on the cold
        # pass (the warm pass legitimately hits its own full prompts)
        assert engine.metrics.counters["requests_completed"] == 10

    def test_paged_through_pallas_kernel_path(self):
        """use_flash=True routes the paged decode through the
        interpret-mode paged kernel: paged-vs-slab streams stay
        BIT-identical because both layouts share the kernel math."""
        tdx.manual_seed(0)
        model = Llama.from_name(
            "tiny", n_kv_heads=2, max_seq_len=64, use_flash=True
        )
        reqs = self._requests(self.SHARED[:3], 0.0, n_new=6)
        _, base = _run_chunked(
            model, 4, reqs, num_slots=2, buckets=(16, 32)
        )
        paged = ServeEngine(
            model, num_slots=2, max_len=64, prefill_buckets=(16, 32),
            decode_chunk=4, page_size=16,
        )
        got = paged.run([dict(r) for r in reqs])
        for a, b in zip(base, got):
            np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_program_count_stable_after_warmup(self):
        """Paged dispatch discipline: one cold + (if hits occur) one
        warm prefill per bucket used, one decode scan — and MORE traffic
        through the warm engine never compiles another program."""
        engine = self._assert_paged_identical(4, self.SHARED, 0.0)
        warm = engine.num_compiled_programs()
        if warm is None:
            pytest.skip("jit cache introspection unavailable on this jax")
        engine.run([dict(r) for r in self._requests(self.SHARED, 0.0)])
        assert engine.num_compiled_programs() == warm

    @pytest.mark.slow
    @pytest.mark.parametrize("k_chunk", [1, 4, 8])
    @pytest.mark.parametrize("mix", [SHARED, DISJOINT, ((None, 7),)])
    @pytest.mark.parametrize("temperature", [0.0, 0.9])
    def test_full_grid_bit_identical(self, k_chunk, mix, temperature):
        self._assert_paged_identical(k_chunk, mix, temperature)


def _fresh_eos_case(model, temperature=0.0, seed=3, idx=3):
    """(prompt, eos, expected stream) with the 4th generated token as
    EOS: with the prefill token at index 0, it lands at in-chunk step
    j = 2 of the first chunk.  The token must be NEW in the stream (one
    that already occurred would stop the request earlier), so this walks
    prompt seeds until the model's own stream has that property — which
    stream a seed gives is the compiler's business, not the engine's."""
    for prompt_seed in range(31, 63):
        prompt = _prompts(prompt_seed, (6,))[0]
        _, base = _run_chunked(
            model, 1,
            [{"prompt": prompt, "max_new_tokens": 20,
              "temperature": temperature, "seed": seed}],
            num_slots=1, buckets=(8,),
        )
        stream = base[0].tokens
        eos = int(stream[idx])
        if eos not in stream[:idx].tolist():  # finishes exactly there
            return prompt, eos, stream[: idx + 1]
    raise AssertionError("no prompt seed gives a fresh 4th token")


class TestFinishMasking:
    """On-device finish mask: a slot finishing at in-chunk step j emits
    nothing after j, freezes its KV position, and the engine accounts
    exactly K - 1 - j masked slot-steps."""

    def _eos_case(self, temperature, seed=3):
        model = _llama()
        prompt, eos, expect = _fresh_eos_case(model, temperature, seed)
        return model, prompt, eos, expect

    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    def test_eos_mid_chunk_masks_remaining_steps(self, temperature):
        k_chunk = 16
        model, prompt, eos, expect = self._eos_case(temperature)
        engine, results = _run_chunked(
            model, k_chunk,
            [{"prompt": prompt, "max_new_tokens": 20,
              "temperature": temperature, "seed": 3}],
            num_slots=1, eos_token=eos, buckets=(8,),
        )
        r = results[0]
        assert r.finish_reason == "stop"
        np.testing.assert_array_equal(r.tokens, expect)  # nothing after j
        # EOS emitted at in-chunk step j = 2 -> K - 1 - j wasted, and the
        # whole of the next chunk: the host cannot foresee an EOS, so the
        # successor was in flight, the slot frozen in it, when it saw this
        counters = engine.metrics.counters
        assert counters["lagged_slot_steps"] == k_chunk
        assert counters["masked_slot_steps"] == k_chunk - 3 + k_chunk
        # the slot's write position froze where the host stopped: 3
        # decode steps consumed (the prefill token rode the prefill
        # dispatch; the EOS token was sampled at step j=2), not K
        frozen = prompt.size + len(expect) - 1
        assert int(engine.cache.pos[0]) == frozen
        # and the device never advanced past it: the masked steps rewrite
        # the frozen row only, so every row past it stayed virgin zeros —
        # an unmasked scan would have written rows up to prompt + K
        k0 = np.asarray(engine.cache.kv[0][0])  # layer 0 K, slot 0 rows
        assert np.all(k0[0, frozen + 1:] == 0)

    def test_masked_steps_zero_when_chunk_fits(self):
        """Requests whose remaining budget is a multiple of K finish at
        the last chunk step: no waste."""
        model = _llama()
        engine, results = _run_chunked(
            model, 4,
            [{"prompt": _prompts(32, (6,))[0], "max_new_tokens": 9}],
            num_slots=1,
        )
        # 1 prefill token + 8 decode tokens = two full K=4 chunks
        assert results[0].finish_reason == "length"
        assert engine.metrics.counters["masked_slot_steps"] == 0
        assert engine.metrics.counters["decode_dispatches"] == 2


class TestPersistentDecode:
    """decode_mode="persistent": ONE while_loop dispatch runs to a
    slot-state fixpoint (or a full ring), the host drains the device
    ring — and the token streams are BIT-identical to the fused K-step
    reference across occupancy x greedy/sampled x shared-prefix/paged,
    because both programs run the same ``_make_decode_body``.  The fast
    tests cover both occupancies, sampling, paging, ring wraparound,
    and the budget-bound exit; the slow sweep runs the full grid."""

    def _requests(self, lengths, temperature, n_new=8):
        return [
            {"prompt": p, "max_new_tokens": n_new,
             "temperature": temperature, "seed": i}
            for i, p in enumerate(_prompts(21, lengths))
        ]

    def _assert_identical(self, lengths, temperature, *, ring=None,
                          page_size=None, n_new=8, **kw):
        model = _llama()
        reqs = self._requests(lengths, temperature, n_new=n_new)
        _, base = _run_chunked(model, 4, reqs)
        engine = ServeEngine(
            model, num_slots=3, max_len=64, prefill_buckets=(16,),
            decode_mode="persistent", ring_capacity=ring,
            page_size=page_size, **kw,
        )
        pers = engine.run([dict(r) for r in reqs])
        for a, b in zip(base, pers):
            assert a.finish_reason == b.finish_reason
            np.testing.assert_array_equal(a.tokens, b.tokens)
        return engine

    def test_greedy_full_and_partial_occupancy_syncs_collapse(self):
        engine = self._assert_identical((6, 11, 9, 4, 13), 0.0)
        snap = engine.metrics.snapshot()
        # THE tentpole invariant: host syncs are exactly the ring
        # drains — prefill defers its fetch, so syncs/token is ~1/wave,
        # not ~1/K (5 requests x 8 tokens through 2 drained waves here)
        assert snap["host_syncs"] == snap["ring_drains"]
        assert snap["loop_iterations"] == snap["decode_steps"]
        assert snap["syncs_per_token"] < 0.11  # vs 0.25 at K=4, 1.1 at K=1
        assert snap["ring_occupancy_hwm"] >= 7  # 7 decode tokens/request
        assert snap["ring_full_drains"] == 0  # default ring = max_len
        self._assert_identical((7,), 0.0)

    def test_sampled_full_and_partial_occupancy(self):
        self._assert_identical((6, 11, 9, 4, 13), 0.9)
        self._assert_identical((7,), 0.9)

    def test_paged_shared_prefix_streams_identical(self):
        rs = np.random.RandomState(17)
        shared = rs.randint(0, 256, (20,)).astype(np.int32)
        reqs = []
        for i, n in enumerate((5, 9, 12)):
            tail = rs.randint(0, 256, (n,)).astype(np.int32)
            reqs.append(
                {"prompt": np.concatenate([shared, tail]),
                 "max_new_tokens": 8, "temperature": 0.0, "seed": i}
            )
        model = _llama()
        _, base = _run_chunked(model, 4, reqs, buckets=(16, 32))
        paged = ServeEngine(
            model, num_slots=3, max_len=64, prefill_buckets=(16, 32),
            decode_mode="persistent", page_size=8,
        )
        cold = paged.run([dict(r) for r in reqs])
        warm = paged.run([dict(r) for r in reqs])  # index now populated
        for a, b, c in zip(base, cold, warm):
            assert a.finish_reason == b.finish_reason == c.finish_reason
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_array_equal(a.tokens, c.tokens)
        assert paged.metrics.counters["prefix_hit_tokens"] > 0

    def test_ring_wraparound_spans_drains(self):
        """A request outliving one ring continues bit-identically from
        its frozen carry at the next dispatch: the ring is reused
        (linear per dispatch), never circularly overwritten in-loop."""
        engine = self._assert_identical((6, 11, 9), 0.0, ring=3)
        snap = engine.metrics.snapshot()
        assert snap["ring_capacity"] == 3
        assert snap["ring_occupancy_hwm"] == 3  # every ring filled
        assert snap["ring_drains"] >= 3  # 7 decode tokens over 3-rings
        assert snap["ring_full_drains"] >= 2
        assert snap["host_syncs"] == snap["ring_drains"]

    def test_budget_bound_exit_resumes(self):
        """Unit view of one budget-bound exit: the loop stops at the
        ring bound with the request unfinished; the host holds exactly
        first-token + ring tokens and the next step resumes."""
        model = _llama()
        engine = ServeEngine(
            model, num_slots=1, max_len=64, prefill_buckets=(16,),
            decode_mode="persistent", ring_capacity=4,
        )
        h = engine.submit(_prompts(21, (6,))[0], max_new_tokens=12)
        engine.step()
        assert not h.done()  # budget-bound exit, not a finish
        assert len(h._request.generated) == 1 + 4  # prefill + one ring
        assert engine.metrics.counters["ring_full_drains"] == 1
        while engine.step():
            pass
        assert h.done() and h.result().finish_reason == "length"
        assert len(h.result().tokens) == 12
        ref = np.asarray(
            generate(model, jnp.asarray(_prompts(21, (6,))[0][None]), 12)
        )[0]
        np.testing.assert_array_equal(
            np.concatenate([_prompts(21, (6,))[0], h.result().tokens]), ref
        )

    def test_eos_first_token_and_one_token_budget(self):
        """fin0 is computed ON DEVICE (the deferred prefill fetch means
        the host can't pre-retire): an EOS first token or an
        already-spent one-token budget must freeze the slot before
        iteration 0 and still finish with the chunked engine's
        reason."""
        model = _llama()
        prompt = _prompts(3, (5,))[0]
        first = int(
            np.asarray(generate(model, jnp.asarray(prompt[None]), 1))[0, -1]
        )
        engine = ServeEngine(
            model, num_slots=1, max_len=64, prefill_buckets=(16,),
            decode_mode="persistent", eos_token=first,
        )
        r = engine.run([{"prompt": prompt, "max_new_tokens": 8}])[0]
        assert r.finish_reason == "stop" and not r.truncated
        np.testing.assert_array_equal(r.tokens, [first])
        engine2 = ServeEngine(
            model, num_slots=2, max_len=64, prefill_buckets=(16,),
            decode_mode="persistent",
        )
        r2 = engine2.run([{"prompt": prompt, "max_new_tokens": 1}])[0]
        assert r2.finish_reason == "length" and len(r2.tokens) == 1

    def test_frozen_slot_rows_stay_virgin(self):
        """A slot finishing mid-loop freezes on device: the masked
        iterations rewrite the frozen row only, so rows past it stay
        virgin zeros (the chunked finish-mask invariant, loop-sized)."""
        model = _llama()
        prompt, eos, expect = _fresh_eos_case(model)
        engine = ServeEngine(
            model, num_slots=2, max_len=64, prefill_buckets=(8,),
            decode_mode="persistent", eos_token=eos,
        )
        # batchmate keeps the loop alive past the first slot's finish
        results = engine.run([
            {"prompt": prompt, "max_new_tokens": 20},
            {"prompt": _prompts(32, (6,))[0], "max_new_tokens": 20,
             "temperature": 0.9, "seed": 5},
        ])
        assert results[0].finish_reason == "stop"
        np.testing.assert_array_equal(results[0].tokens, expect)
        frozen = prompt.size + len(results[0].tokens) - 1
        k0 = np.asarray(engine.cache.kv[0][0])  # layer 0 K, slot 0 rows
        assert np.all(k0[0, frozen + 1:] == 0)
        assert engine.metrics.counters["masked_slot_steps"] > 0

    def test_stream_tail_matches_drain(self):
        """Opt-in streamed tail: callbacks fire per loop iteration and
        change nothing about the (authoritative) drained streams."""
        engine = self._assert_identical(
            (6, 11), 0.0, persistent_stream=True
        )
        assert engine.stream_supported == "io_callback"
        assert engine.metrics.counters["stream_callbacks"] > 0

    def test_program_count_stable_after_warmup(self):
        engine = self._assert_identical((6, 9), 0.0)
        warm = engine.num_compiled_programs()
        if warm is None:
            pytest.skip("jit cache introspection unavailable on this jax")
        engine.run([dict(r) for r in self._requests((5, 12, 8), 0.0)])
        assert engine.num_compiled_programs() == warm

    def test_validation(self):
        with pytest.raises(ValueError, match="decode_mode"):
            ServeEngine(_llama(), max_len=32, decode_mode="turbo")
        with pytest.raises(ValueError, match="ring_capacity"):
            ServeEngine(_llama(), max_len=32, ring_capacity=8)
        with pytest.raises(ValueError, match="ring_capacity"):
            ServeEngine(
                _llama(), max_len=32, decode_mode="persistent",
                ring_capacity=0,
            )
        with pytest.raises(ValueError, match="persistent_stream"):
            ServeEngine(_llama(), max_len=32, persistent_stream=True)

    def test_metrics_geometry_in_json_and_prom(self):
        """The ISSUE-6 metric satellite: ring counters in to_json() and
        the Prometheus exposition, ring gauges only when persistent."""
        from torchdistx_tpu.obs import MetricsRegistry
        from torchdistx_tpu.serve.metrics import ServeMetrics as SM

        m = SM(num_slots=2, ring_capacity=16)
        m.count("loop_iterations", 9)
        m.count("ring_drains", 2)
        m.observe_ring(7)
        j = m.to_json()
        assert j["counters"]["loop_iterations"] == 9
        assert j["counters"]["ring_drains"] == 2
        assert j["gauges"]["ring_capacity"] == 16
        assert j["gauges"]["ring_occupancy_hwm"] == 7
        reg = MetricsRegistry()
        reg.register_collector(m.collector(), obj=m)
        text = reg.render()
        assert "tdx_serve_ring_drains_total 2" in text
        assert "tdx_serve_ring_occupancy_hwm 7" in text
        # chunked engines carry the counters (zero) but not the gauges
        assert "ring_capacity" not in SM(num_slots=2).to_json()["gauges"]

    @pytest.mark.slow
    @pytest.mark.parametrize("ring", [None, 3])
    @pytest.mark.parametrize("page_size", [None, 8])
    @pytest.mark.parametrize("lengths", [(6, 11, 9, 4, 13), (7,)])
    @pytest.mark.parametrize("temperature", [0.0, 0.9])
    def test_full_grid_bit_identical(self, ring, page_size, lengths,
                                     temperature):
        self._assert_identical(
            lengths, temperature, ring=ring, page_size=page_size
        )


class TestSchedulerUnit:
    def _req(self, n=4, **kw):
        return Request(
            rid=-1, prompt=np.zeros(n, np.int32), max_new_tokens=4, **kw
        )

    def test_fcfs_blocked_head_blocks_line(self):
        s = Scheduler(num_slots=2, max_tokens_in_flight=16)
        a, b, c = self._req(4), self._req(12), self._req(2)
        for r in (a, b, c):
            s.submit(r)
        admitted = s.admit(now=0.0)
        # a (cost 8) admitted; b (cost 16) over budget; c must NOT skip b
        assert [r.rid for r, _ in admitted] == [a.rid]
        assert s.queue_depth == 2
        s.retire(a)
        assert [r.rid for r, _ in s.admit(now=0.0)] == [b.rid]

    def test_slots_reused_lowest_first(self):
        s = Scheduler(num_slots=2)
        a, b = self._req(), self._req()
        s.submit(a), s.submit(b)
        assert [slot for _, slot in s.admit(now=0.0)] == [0, 1]
        s.retire(a)
        c = self._req()
        s.submit(c)
        assert [slot for _, slot in s.admit(now=0.0)] == [0]

    def test_retire_requires_running(self):
        s = Scheduler(num_slots=1)
        r = self._req()
        s.submit(r)
        with pytest.raises(ValueError, match="not running"):
            s.retire(r)


class TestKVCacheUnit:
    def test_admit_retire_bookkeeping(self):
        cache = SlotKVCache(_llama(), num_slots=2, max_len=16)
        cache.admit(0, 5)
        assert cache.active_count == 1 and cache.pos[0] == 5
        with pytest.raises(ValueError, match="already active"):
            cache.admit(0, 3)
        cache.advance_slot(0)
        assert cache.pos[0] == 6 and cache.pos[1] == 0
        cache.retire(0)
        assert cache.active_count == 0
        with pytest.raises(ValueError, match="outside"):
            cache.admit(1, 17)

    def test_positions_clamped_for_dead_slots(self):
        cache = SlotKVCache(_llama(), num_slots=1, max_len=4)
        cache.pos[0] = 9  # stale beyond geometry
        assert cache.positions()[0] == 3


class TestMixedKinds:
    """A cache whose layers hold different kinds of entry
    (``kv_cache.entry_kind``): a Jamba stack's recurrent state beside
    the KV rows of its attention layers.  The kind is the entry's TYPE,
    layer by layer; nothing is read off layer 0 or an entry's length."""

    @staticmethod
    def _jamba():
        from torchdistx_tpu.models import Jamba

        tdx.manual_seed(5)
        return Jamba.from_name("tiny")  # layers: s p s s p s

    def test_kinds_and_sizes_go_layer_by_layer(self):
        from torchdistx_tpu.serve.kv_cache import RecurrentState, cache_kinds

        model = self._jamba()
        cfg = model.cfg
        kinds = ("state", "pair", "state", "state", "pair", "state")
        assert cache_kinds(model) == kinds
        cache = SlotKVCache(model, num_slots=3, max_len=16)
        assert cache.kinds == kinds and not cache.latent
        assert cache.kv_heads == 1  # of the layers that have heads
        assert isinstance(cache.kv[0], RecurrentState)
        assert type(cache.kv[1]) is tuple and len(cache.kv[1]) == 2
        # a pair is stored with its head tail merged, a state as the model makes it
        assert cache.kv[1][0].shape == (3, 16, cfg.n_kv_heads * cfg.head_dim)
        assert cache.kv[0].conv.shape == (3, 3 * cfg.d_inner)
        assert cache.kv[0].ssm.shape == (3, cfg.d_state, cfg.d_inner)
        assert cache.kv[0].ssm.dtype == jnp.float32
        row = 2 * cfg.n_kv_heads * cfg.head_dim * 4  # K and V, float32
        state = 4 * (cfg.d_state * cfg.d_inner * 4 + 3 * cfg.d_inner * 4)
        assert cache.kv_row_bytes == row
        assert cache.state_slot_bytes == state
        assert cache.kv_data_nbytes == 2 * 3 * 16 * row  # rows only
        assert cache.kv_scale_nbytes == 0
        assert cache.nbytes == cache.kv_data_nbytes + 3 * state
        assert cache.kv_dtype_name == "float32"
        assert all(a.committed for entry in cache.kv for a in entry)

    def test_a_row_dtype_casts_the_rows_and_never_the_state(self):
        cache = SlotKVCache(
            self._jamba(), num_slots=2, max_len=16, kv_dtype="bfloat16"
        )
        assert cache.kv[1][0].dtype == jnp.bfloat16
        assert cache.kv_dtype_name == "bfloat16"
        assert cache.kv[0].ssm.dtype == jnp.float32
        assert cache.kv[0].conv.dtype == jnp.float32  # the model's

    def test_int8_is_refused_over_what_has_no_heads(self):
        with pytest.raises(ValueError, match="recurrent state"):
            SlotKVCache(self._jamba(), num_slots=2, max_len=16, kv_dtype="int8")

    def test_write_slot_replaces_a_state_whole_and_a_pairs_rows(self):
        from torchdistx_tpu.serve.kv_cache import RecurrentState, write_slot

        model = self._jamba()
        cache = SlotKVCache(model, num_slots=3, max_len=16)
        before = jax.tree_util.tree_map(lambda a: a + 1.0, cache.kv)
        slab = jax.tree_util.tree_map(
            lambda a: jnp.full(a.shape, 7.0, a.dtype), model.init_cache(1, 8)
        )
        after = jax.jit(write_slot)(before, slab, jnp.int32(1))
        assert [type(e) for e in after] == [type(e) for e in before]
        for old, new in zip(before, after):
            if isinstance(new, RecurrentState):
                for o, n in zip(old, new):  # slot 1 whole, the others as they were
                    assert np.all(np.asarray(n[1]) == 7.0)
                    np.testing.assert_array_equal(n[0], o[0])
                    np.testing.assert_array_equal(n[2], o[2])
            else:
                for o, n in zip(old, new):  # the slab's 8 rows of slot 1
                    assert np.all(np.asarray(n[1, :8]) == 7.0)
                    np.testing.assert_array_equal(n[1, 8:], o[1, 8:])
                    np.testing.assert_array_equal(n[0], o[0])

    def test_a_plain_pair_cache_is_what_it_was(self):
        """The models' ``(k, v)`` contract needs no type: plain tuples
        in, plain tuples stored, every layer a pair."""
        cache = SlotKVCache(_llama(), num_slots=2, max_len=16)
        assert set(cache.kinds) == {"pair"} and not cache.latent
        assert all(type(e) is tuple and len(e) == 2 for e in cache.kv)
        assert cache.state_slot_bytes == 0
        assert cache.kv_data_nbytes == cache.nbytes
        quant = SlotKVCache(_llama(), num_slots=2, max_len=16, kv_dtype="int8")
        assert all(type(e) is tuple and len(e) == 4 for e in quant.kv)
        assert quant.kv_scale_nbytes > 0 and quant.kv_dtype_name == "int8"
        assert quant.kv_data_nbytes * 4 == cache.kv_data_nbytes


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested jaxprs (jit, scan, while, the
    vmapped write's loop) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


class TestStoredLayout:
    """The engine stores K/V as the decode kernel's operand — head tail
    merged, ``(lead, rows, Hkv * D)`` — so the compiled decode program
    hands the stored array to the kernel as it is.  On the chip a
    ``(…, Hkv, D)`` array and its ``(…, Hkv * D)`` "view" are tiled
    differently and the reshape between them copies the array;
    tests/test_chip_compile.py pins the compiled program, this pins the
    storage, the values and the traced program."""

    N_NEW = 9  # the prefill's token + 8 decode steps
    MAX_LEN = 48  # no weight of the tiny model has a cache array's size

    def _engine(self, paged, quantized, use_flash=None):
        tdx.manual_seed(0)
        model = Llama.from_name(
            "tiny", n_kv_heads=2, max_seq_len=64, use_flash=use_flash
        )
        opts = dict(page_size=8) if paged else {}
        if quantized:
            opts["kv_dtype"] = "int8"
        return model, ServeEngine(
            model, num_slots=2, max_len=self.MAX_LEN,
            prefill_buckets=(16,), **opts
        )

    @pytest.mark.parametrize("quantized", [False, True], ids=["plain", "int8"])
    @pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
    def test_storage_stream_and_decode_program(self, paged, quantized):
        model, engine = self._engine(paged, quantized)
        cfg = model.cfg
        hkv, d = cfg.n_kv_heads, cfg.head_dim
        lead = (
            (engine.num_pages, engine.page_size)
            if paged
            else (2, self.MAX_LEN)
        )
        # -- storage: rank 3, tail Hkv * D (scales: Hkv)
        assert engine.cache.kv_heads == hkv
        assert len(engine.cache.kv) == cfg.n_layers
        for entry in engine.cache.kv:
            assert len(entry) == (4 if quantized else 2)
            for a in entry[:2]:
                assert a.shape == (*lead, hkv * d)
            for a in entry[2:]:
                assert a.shape == (*lead, hkv) and a.dtype == jnp.float32
        # -- values: write_slot (paged: the suffix scatter) + 8 decode
        # steps.  The model-dtype cache is bit-identical to generate();
        # int8 to the other geometry's int8 stream, and its first token
        # (sampled from the unquantized prefill) to generate()'s
        (prompt,) = _prompts(7, (11,))
        (res,) = engine.run(
            [{"prompt": prompt, "max_new_tokens": self.N_NEW}]
        )
        ref = np.asarray(
            generate(model, jnp.asarray(prompt[None]), self.N_NEW)
        )[0, len(prompt):]
        if quantized:
            _, other = self._engine(not paged, True)
            (twin,) = other.run(
                [{"prompt": prompt, "max_new_tokens": self.N_NEW}]
            )
            np.testing.assert_array_equal(res.tokens, twin.tokens)
            assert res.tokens[0] == ref[0]
        else:
            np.testing.assert_array_equal(res.tokens, ref)
        # -- the traced decode program on the KERNEL path: the cache
        # reaches pallas_call without a reshape, transpose or copy
        kmodel, kengine = self._engine(paged, quantized, use_flash=True)
        extra = (
            (jnp.asarray(kengine.cache.page_tables),) if paged else ()
        )
        jaxpr = jax.make_jaxpr(
            lambda kv: kmodel.forward_decode(
                jnp.zeros((2, 1), jnp.int32), kv,
                jnp.asarray([3, 5], jnp.int32), *extra,
            )
        )(kengine.cache.kv)
        n = int(np.prod(lead)) * hkv * d
        names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
        assert names.count("pallas_call") == cfg.n_layers
        relayouts = [
            f"{e.primitive.name}{tuple(v.aval.shape for v in e.invars)}"
            for e in _eqns(jaxpr.jaxpr)
            if e.primitive.name in ("reshape", "transpose", "copy")
            and any(
                int(np.prod(v.aval.shape)) == n
                for v in e.invars if hasattr(v.aval, "shape")
            )
        ]
        assert not relayouts, relayouts

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_cache_is_made_stored_never_as_a_second_copy(self, kv_dtype):
        """``init_cache``'s model-layout arrays exist only inside the
        one jitted program that makes the stored ones: built eagerly,
        both would be alive at once — on the chip that was 3.2 GB too
        many next to 11 GB of Mistral-7B weights (PR 28's first call)."""
        model, concrete = _llama(), []
        make = model.init_cache

        def spy(*args, **kwargs):
            out = make(*args, **kwargs)
            concrete.append(not isinstance(out[0][0], jax.core.Tracer))
            return out

        model.init_cache = spy
        cache = SlotKVCache(model, num_slots=2, max_len=16, kv_dtype=kv_dtype)
        assert concrete and not any(concrete)
        assert all(a.committed for entry in cache.kv for a in entry)

    def test_merge_and_split_are_inverse_views(self):
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(3, 5, 2, 8), jnp.float32)
        assert merge_heads(x).shape == (3, 5, 16)
        np.testing.assert_array_equal(
            np.asarray(split_heads(merge_heads(x), 2)), np.asarray(x)
        )
        scale = x[..., :1]  # (…, Hkv, 1), as quantize_kv makes scales
        assert merge_heads(scale).shape == (3, 5, 2)
        assert split_heads(merge_heads(scale), 2).shape == (3, 5, 2, 1)


class TestShardedParams:
    def test_fsdp_sharded_params_serve_and_match_generate(self, mesh8):
        # the advertised params= override with mesh-committed (FSDP)
        # params: the slot cache must follow the params onto the mesh
        # (replicated) or the first dispatch dies with an
        # incompatible-devices jit error
        from jax.sharding import NamedSharding

        from torchdistx_tpu.parallel.fsdp import fsdp_partition_spec

        model = _llama()
        params = {
            name: jax.device_put(
                p,
                NamedSharding(
                    mesh8, fsdp_partition_spec(p.shape, mesh8, "fsdp")
                ),
            )
            for name, p in model.named_parameters()
        }
        engine = ServeEngine(
            model, num_slots=2, max_len=64, prefill_buckets=(16,),
            params=params,
        )
        prompts = _prompts(10, (6, 9))
        results = engine.run(
            [{"prompt": p, "max_new_tokens": 5} for p in prompts]
        )
        for p, r in zip(prompts, results):
            assert r.finish_reason == "length"
            ref = np.asarray(
                generate(model, jnp.asarray(p[None]), 5, params=params)
            )[0]
            np.testing.assert_array_equal(
                np.concatenate([p, r.tokens]), ref
            )


class TestValidation:
    def test_submit_rejects_oversized_and_empty(self):
        engine = ServeEngine(_llama(), num_slots=1, max_len=32)
        with pytest.raises(ValueError, match="exceeds the slot cache"):
            engine.submit(np.zeros(30, np.int32), max_new_tokens=10)
        with pytest.raises(ValueError, match="at least one token"):
            engine.submit(np.zeros(0, np.int32), max_new_tokens=4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.submit(np.zeros(4, np.int32), max_new_tokens=0)

    def test_engine_rejects_bad_geometry(self):
        with pytest.raises(ValueError, match="exceeds the model"):
            ServeEngine(_llama(), max_len=1024)
        with pytest.raises(ValueError, match="top_k"):
            ServeEngine(_llama(), max_len=32, top_k=0)
        with pytest.raises(ValueError, match="decode_chunk"):
            ServeEngine(_llama(), max_len=32, decode_chunk=0)

    def test_prompt_beyond_largest_bucket_raises_at_submit(self):
        """Regression: explicit prefill_buckets are taken as given (no
        silent max_len bucket appended), so a prompt longer than the
        largest bucket must die with a clear ValueError in submit(),
        never inside the prefill jit."""
        engine = ServeEngine(
            _llama(), num_slots=1, max_len=64, prefill_buckets=(8, 16)
        )
        assert engine.prefill_buckets == (8, 16)  # nothing appended
        with pytest.raises(ValueError, match="largest prefill bucket"):
            engine.submit(np.zeros(20, np.int32), max_new_tokens=4)
        # up to the largest bucket still serves fine
        r = engine.run(
            [{"prompt": _prompts(40, (16,))[0], "max_new_tokens": 3}]
        )[0]
        assert r.finish_reason == "length"

    def test_prompt_beyond_room_for_max_new_raises_at_submit(self):
        engine = ServeEngine(_llama(), num_slots=1, max_len=32)
        with pytest.raises(ValueError, match="at most 12 tokens"):
            engine.submit(np.zeros(13, np.int32), max_new_tokens=20)


class TestMetricsUnit:
    def test_histogram_snapshot(self):
        h = Histogram()
        assert h.snapshot()["count"] == 0
        for v in range(1, 101):
            h.record(float(v))
        s = h.snapshot()
        assert s["count"] == 100 and s["max"] == 100.0
        assert abs(s["mean"] - 50.5) < 1e-9
        assert 49 <= s["p50"] <= 52 and 94 <= s["p95"] <= 97

    def test_snapshot_is_json_serializable(self):
        import json

        m = ServeMetrics(num_slots=4)
        m.count("tokens_generated", 9)
        m.count("tokens_decoded", 7)  # 2 of the 9 rode prefill dispatches
        m.observe_gauges(queue_depth=2, active_slots=3)
        m.decode_s.record(0.5)
        snap = m.snapshot()
        parsed = json.loads(json.dumps(snap))
        assert parsed["tokens_generated"] == 9
        assert parsed["queue_depth"] == 2
        assert parsed["slot_occupancy_mean"] == 0.75
        # decode throughput excludes prefill-sampled tokens
        assert parsed["decode_tokens_per_sec"] == 14.0


class TestHBMBudgetGate:
    """The capacity planner's second admission gate (ISSUE 8): an
    engine whose projected peak (weights + KV + per-program temps)
    exceeds ``hbm_budget`` refuses admission with the NAMED reason
    ``hbm_budget`` in the request's lifecycle events plus the
    ``admissions_rejected_hbm`` counter — and admits once the budget is
    raised.  The paged variant pins that the page gate ALONE would have
    admitted (pages were free; only the budget refused)."""

    def test_slab_engine_refuses_then_admits(self):
        engine = ServeEngine(_llama(), num_slots=2, max_len=64, hbm_budget=1)
        h = engine.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
        for _ in range(3):
            engine.step()
        assert not h.done()
        assert engine.scheduler.queue_depth == 1
        assert engine.metrics.counters["admissions_rejected_hbm"] == 3
        gated = [e for e in h._request.events if e[0] == "gated"]
        assert gated and gated[-1][2]["why"] == "hbm_budget"
        # the gate is live: raising the budget re-admits on the next tick
        engine.hbm_budget = 10**15
        while engine.step():
            pass
        assert h.done() and h.result().finish_reason == "length"
        # reason + counter survive into the terminal result's event log
        assert any(
            e[0] == "gated" and (e[2] or {}).get("why") == "hbm_budget"
            for e in h.result().events
        )

    def test_paged_engine_page_gate_alone_would_admit(self):
        engine = ServeEngine(
            _llama(), num_slots=2, max_len=64, page_size=16, hbm_budget=1
        )
        prompt = np.arange(1, 9, dtype=np.int32)
        need = -(-(prompt.size + 4) // engine.page_size)
        assert engine.pool.free_count >= need  # pages were no obstacle
        h = engine.submit(prompt, max_new_tokens=4)
        engine.step()
        assert not h.done()
        assert engine.metrics.counters["admissions_rejected_hbm"] == 1
        # the budget refusal fired BEFORE the page gate: nothing was
        # reserved, so a later admit starts from a clean reservation
        assert engine.pool.in_use == 0
        assert h._request.pages is None
        engine.hbm_budget = None  # disable the gate entirely
        while engine.step():
            pass
        assert h.done() and h.result().finish_reason == "length"

    def test_budget_with_headroom_admits_immediately(self):
        engine = ServeEngine(
            _llama(), num_slots=2, max_len=64, hbm_budget=10**15
        )
        r = engine.run(
            [{"prompt": np.arange(1, 9, dtype=np.int32),
              "max_new_tokens": 3}]
        )[0]
        assert r.finish_reason == "length"
        assert engine.metrics.counters["admissions_rejected_hbm"] == 0

    def test_memory_plan_schema(self):
        engine = ServeEngine(_llama(), num_slots=2, max_len=64)
        plan = engine.memory_plan(budget_bytes=10**12)
        assert plan["schema"] == "tdx-capacity-v1"
        assert plan["components"]["kv_cache"] == engine.cache.nbytes
        assert plan["components"]["weights"] > 0
        assert plan["fits"] is True and plan["headroom_bytes"] > 0


def _llama_tp():
    # default n_kv_heads (= n_heads = 4): divisible by every tp in the
    # grid.  (_llama's n_kv_heads=2 is the divisibility-ERROR case.)
    tdx.manual_seed(0)
    return Llama.from_name("tiny", max_seq_len=64)


def _tp_mesh(tp):
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:tp]), ("tp",))


def _serve_vs_generate(model, engine, prompts, max_new=6):
    """Drive the engine and pin every greedy stream bit-identical to
    the single-device ``generation.generate`` reference."""
    results = engine.run(
        [{"prompt": p, "max_new_tokens": max_new} for p in prompts]
    )
    for p, r in zip(prompts, results):
        assert r.finish_reason == "length" and not r.truncated
        ref = np.asarray(generate(model, jnp.asarray(p[None]), max_new))[0]
        np.testing.assert_array_equal(
            np.concatenate([p, r.tokens]), ref
        )


class TestTPServing:
    """Mesh-parallel serving: params Megatron-sharded (llama_tp_rule),
    KV slabs/pools sharded over the head axis, page tables host-side —
    and every greedy stream still bit-identical to the single-device
    reference (CPU mesh: column-parallel matmuls are exact per element
    and the tiny head-sharded reductions do not reorder a greedy
    argmax).  Fast siblings here; the full tp x K x mode x layout grid
    is the -m slow sweep below."""

    def test_tp2_slab_fused_matches_single_device(self):
        model = _llama_tp()
        engine = ServeEngine(
            model, num_slots=3, max_len=64, prefill_buckets=(16,),
            decode_chunk=4, mesh=_tp_mesh(2),
        )
        assert engine.tp == 2
        _serve_vs_generate(model, engine, _prompts(21, (6, 11, 9, 4, 13)))
        # the KV cache is genuinely head-sharded: each device addresses
        # half the slab bytes, and the admission input reports per-shard
        kv = engine.cache.kv[0][0]
        shard = kv.sharding.shard_shape(kv.shape)
        assert shard[2] == kv.shape[2] // 2
        assert (
            engine.memory_plan()["components"]["kv_cache"]
            == engine.cache.nbytes // 2
        )

    def test_plan_rule_in_the_models_layout_maps_onto_the_stored_array(self):
        """A plan may state its ``kv_cache`` rule against the model's
        (lead, rows, Hkv, D): the head entry lands on the merged axis of
        the stored (lead, rows, Hkv * D) array; a rule that splits
        head_dim has no axis to split and is refused by name."""
        from jax.sharding import PartitionSpec as P

        from torchdistx_tpu.parallel.plan import ShardingPlan
        from torchdistx_tpu.serve.engine import _cache_sharding

        mesh = _tp_mesh(2)

        def placed(spec):
            plan = ShardingPlan(mesh, rules=((r"^kv_cache$", spec),))
            return _cache_sharding({}, mesh=mesh, kv_heads=2, plan=plan).spec

        assert placed(P(None, None, "tp", None)) == P(None, None, "tp")
        assert placed(P(None, None, "tp")) == P(None, None, "tp")
        assert _cache_sharding({}, mesh=mesh, kv_heads=2).spec == P(
            None, None, "tp"
        )
        with pytest.raises(ValueError, match="shards head_dim"):
            placed(P(None, None, None, "tp"))

    def test_tp2_paged_persistent_matches_single_device(self):
        model = _llama_tp()
        engine = ServeEngine(
            model, num_slots=2, max_len=64, prefill_buckets=(16,),
            decode_mode="persistent", page_size=16, mesh=_tp_mesh(2),
        )
        _serve_vs_generate(model, engine, _prompts(22, (5, 12, 9)))

    def test_tp_mesh_comm_audit_pins_closed_form(self):
        from torchdistx_tpu.obs.comm import comm_audit

        model = _llama_tp()
        engine = ServeEngine(
            model, num_slots=2, max_len=64, prefill_buckets=(16,),
            decode_chunk=4, mesh=_tp_mesh(2),
        )
        with comm_audit() as prof:
            engine.run(
                [{"prompt": p, "max_new_tokens": 6}
                 for p in _prompts(23, (7, 10))]
            )
        c = engine.metrics.counters
        nl, dim = model.cfg.n_layers, model.cfg.dim
        # 2 all-reduces per block (attention out + MLP down), per
        # prefill dispatch and per on-device decode step
        expected_ops = 2 * nl * (c["prefill_calls"] + c["decode_steps"])
        assert prof.ops("all_reduce", "tp") == expected_ops
        # payload: n_tokens x dim x 4B per all-reduce — prefills carry
        # their padded bucket, decode steps carry num_slots rows
        expected_payload = (
            2 * nl * 4 * dim
            * (c["tokens_prefilled"] + c["decode_steps"] * engine.num_slots)
        )
        assert prof.payload_bytes("all_reduce", "tp") == expected_payload
        # ring all-reduce wire ratio 2(n-1)/n = 1.0 at tp=2
        assert prof.wire_bytes("all_reduce", "tp") == expected_payload
        # single-device engines record nothing (guards fingerprinted
        # expectations: the tp=1 rows must stay collective-free)
        single = ServeEngine(
            _llama_tp(), num_slots=2, max_len=64, prefill_buckets=(16,)
        )
        with comm_audit() as empty:
            single.run([{"prompt": _prompts(23, (7,))[0],
                         "max_new_tokens": 4}])
        assert empty.ops() == 0

    def test_kv_head_divisibility_error(self):
        # _llama: n_kv_heads=2 — a 4-way tp mesh cannot shard the head
        # axis; the constructor must say so, not die inside jit
        with pytest.raises(ValueError, match="does not divide"):
            ServeEngine(_llama(), num_slots=2, max_len=64,
                        mesh=_tp_mesh(4))

    def test_mesh_axis_and_rule_validation(self):
        from jax.sharding import Mesh

        bad = Mesh(np.asarray(jax.devices()[:2]), ("data",))
        with pytest.raises(ValueError, match="tp_axis"):
            ServeEngine(_llama_tp(), num_slots=1, max_len=32, mesh=bad)
        from torchdistx_tpu.parallel.tp import llama_tp_plan

        with pytest.raises(ValueError, match="requires mesh"):
            ServeEngine(
                _llama_tp(), num_slots=1, max_len=32,
                plan=llama_tp_plan(_tp_mesh(2)),
            )


@pytest.mark.slow
class TestTPServingSlowGrid:
    """The pinned grid of the issue: tp in {1,2,4} x K in {1,4} x
    {chunked,persistent} x {slab,paged}, every greedy stream
    bit-identical to the single-device reference."""

    @pytest.mark.parametrize("tp", [1, 2, 4])
    @pytest.mark.parametrize("k_chunk", [1, 4])
    @pytest.mark.parametrize("mode", ["chunked", "persistent"])
    @pytest.mark.parametrize("paged", [False, True])
    def test_grid(self, tp, k_chunk, mode, paged):
        model = _llama_tp()
        kw = dict(
            num_slots=2, max_len=64, prefill_buckets=(16,),
            mesh=_tp_mesh(tp),
        )
        if mode == "persistent":
            if k_chunk != 1:
                pytest.skip("persistent mode has no decode_chunk")
            kw["decode_mode"] = "persistent"
        else:
            kw["decode_chunk"] = k_chunk
        if paged:
            kw["page_size"] = 16
        engine = ServeEngine(model, **kw)
        _serve_vs_generate(model, engine, _prompts(31, (6, 13, 9)))


class TestChunkedPrefill:
    """Chunked prefill: a long-prompt admission is split into
    bucket-sized chunks with a decode dispatch interleaved between
    them, so active slots keep emitting — and the streams stay
    bit-identical (interleaving is latency-only)."""

    def _ab(self, *, paged=False, mesh=None):
        model = _llama_tp()
        kw = dict(
            num_slots=3, max_len=64, prefill_buckets=(16, 64),
            decode_chunk=2,
        )
        if paged:
            kw["page_size"] = 16
        if mesh is not None:
            kw["mesh"] = mesh
        plain = ServeEngine(model, **kw)
        chunked = ServeEngine(model, **kw, chunked_prefill=16)

        def scenario(engine):
            shorts = [
                engine.submit(p, max_new_tokens=20)
                for p in _prompts(41, (5, 9))
            ]
            engine.step()
            engine.step()
            long_h = engine.submit(
                _prompts(42, (40,))[0], max_new_tokens=6
            )
            while engine.step():
                pass
            return [h.result() for h in shorts], long_h.result()

        return model, plain, chunked, scenario

    def test_decode_slots_emit_between_chunks(self):
        _, plain, chunked, scenario = self._ab()
        shorts_a, long_a = scenario(plain)
        shorts_b, long_b = scenario(chunked)
        c = chunked.metrics.counters
        assert c["chunked_prefills"] == 1
        # 40-token prompt, threshold 16: chunks of 16+16+8 (the tail
        # rides its own bucket-16 dispatch)
        assert c["prefill_chunks"] == 3
        assert c["prefill_interleaved_dispatches"] == 2
        assert plain.metrics.counters["chunked_prefills"] == 0
        # the latency claim: short slots received tokens BETWEEN the
        # long prompt's chunks.  The long request's first decode block is
        # the first dispatch after its last chunk; the interleaved
        # dispatches are the ones just before it, and every short request
        # was riding them (its blocks reach from before to after)
        first = long_b.first_decode_cycle
        between = range(
            first - c["prefill_interleaved_dispatches"], first
        )
        for r in shorts_b:
            assert r.first_decode_cycle < between[0]
            assert r.last_decode_cycle >= between[-1], (
                "no decode dispatch landed between chunks"
            )
        # unchunked, the long prompt stalled them: no dispatch lies
        # between its admission and its own first block
        assert plain.metrics.counters["prefill_interleaved_dispatches"] == 0
        # and chunking changed WHEN, never WHAT: all streams identical
        for ra, rb in zip(shorts_a + [long_a], shorts_b + [long_b]):
            np.testing.assert_array_equal(ra.tokens, rb.tokens)

    def test_paged_chunked_prefill_streams_identical(self):
        _, plain, chunked, scenario = self._ab(paged=True)
        shorts_a, long_a = scenario(plain)
        shorts_b, long_b = scenario(chunked)
        assert chunked.metrics.counters["chunked_prefills"] == 1
        assert chunked.metrics.counters["prefill_interleaved_dispatches"] > 0
        for ra, rb in zip(shorts_a + [long_a], shorts_b + [long_b]):
            np.testing.assert_array_equal(ra.tokens, rb.tokens)

    def test_tp_mesh_chunked_prefill_streams_identical(self):
        _, plain, chunked, scenario = self._ab(mesh=_tp_mesh(2))
        shorts_a, long_a = scenario(plain)
        shorts_b, long_b = scenario(chunked)
        assert chunked.metrics.counters["prefill_interleaved_dispatches"] > 0
        for ra, rb in zip(shorts_a + [long_a], shorts_b + [long_b]):
            np.testing.assert_array_equal(ra.tokens, rb.tokens)

    def test_chunked_prefill_requires_bucket(self):
        with pytest.raises(ValueError, match="must be one of"):
            ServeEngine(
                _llama_tp(), num_slots=1, max_len=64,
                prefill_buckets=(16, 64), chunked_prefill=12,
            )

    def test_short_prompts_never_chunk(self):
        engine = ServeEngine(
            _llama_tp(), num_slots=1, max_len=64,
            prefill_buckets=(16, 64), chunked_prefill=16,
        )
        r = engine.run(
            [{"prompt": _prompts(43, (10,))[0], "max_new_tokens": 4}]
        )[0]
        assert r.finish_reason == "length"
        assert engine.metrics.counters["chunked_prefills"] == 0
