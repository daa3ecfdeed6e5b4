"""Shared-prefix paged KV cache (serve/prefix_cache.py + the paged side
of serve/kv_cache.py and the engine integration).

The load-bearing invariants:

- **Allocator discipline**: pages are refcounted; the scratch page is
  never handed out; a page returns to the free list only when no table
  and no index entry references it.
- **Radix index semantics**: matches are full-page, page-aligned, and
  capped at ``len(prompt) - 1`` tokens (the last prompt token's logits
  must be computed); insertion adopts pages with the index's own
  refcount; eviction is LRU over leaves and never touches a page a
  running request references.
- **No KV leakage across page reuse**: a short request admitted into a
  retired long request's pages produces a stream bit-identical to a
  fresh engine's — the paged rewrite of the slab stale-row regression.
- **Admission gates on pages**: a pool smaller than the worst-case
  footprint defers requests (FCFS) instead of corrupting streams, and
  submit() rejects requests that could NEVER fit.

Engine-level bit-identity of paged-vs-slab streams across the
K x occupancy x prefix-mix grid lives in tests/test_serve.py.
"""

import numpy as np
import pytest

import torchdistx_tpu as tdx
from torchdistx_tpu.models import Llama
from torchdistx_tpu.serve import PagePool, RadixPrefixIndex, ServeEngine
from torchdistx_tpu.serve.prefix_cache import SCRATCH_PAGE


def _llama():
    tdx.manual_seed(0)
    return Llama.from_name("tiny", n_kv_heads=2, max_seq_len=64)


def _prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 256, (n,)).astype(np.int32) for n in lengths]


class TestPagePool:
    def test_alloc_lowest_first_and_scratch_reserved(self):
        pool = PagePool(6)
        assert pool.capacity == 5
        pages = pool.alloc(3)
        assert pages == [1, 2, 3]  # SCRATCH_PAGE = 0 never allocated
        assert SCRATCH_PAGE not in pages
        assert pool.in_use == 3 and pool.free_count == 2

    def test_refcount_lifecycle(self):
        pool = PagePool(4)
        (p,) = pool.alloc(1)
        pool.incref([p])
        assert pool.decref([p]) == 0  # one holder left
        assert pool.free_count == 2
        assert pool.decref([p]) == 1  # now free
        assert pool.free_count == 3
        with pytest.raises(RuntimeError, match="decref of free"):
            pool.decref([p])
        with pytest.raises(RuntimeError, match="incref of free"):
            pool.incref([p])

    def test_freed_pages_reallocate_lowest_first(self):
        pool = PagePool(5)
        a = pool.alloc(3)  # [1, 2, 3]
        pool.decref([a[1]])  # free page 2
        pool.decref([a[0]])  # free page 1
        assert pool.alloc(2) == [1, 2]

    def test_over_allocation_is_a_bug_not_backpressure(self):
        pool = PagePool(3)
        with pytest.raises(RuntimeError, match="over-allocated"):
            pool.alloc(3)

    def test_high_water(self):
        pool = PagePool(6)
        a = pool.alloc(4)
        pool.decref(a)
        pool.alloc(1)
        assert pool.high_water == 4

    def test_too_small(self):
        with pytest.raises(ValueError, match="num_pages"):
            PagePool(1)


class TestRadixPrefixIndex:
    def _toks(self, *vals):
        return np.asarray(vals, np.int32)

    def test_match_is_page_aligned_and_caps_at_last_token(self):
        pool, idx = PagePool(8), RadixPrefixIndex(page_size=4)
        pages = pool.alloc(2)
        idx.insert(self._toks(*range(8)), pages, pool)
        # full prompt == cached tokens: the LAST token must be computed,
        # so only the first page may be served from cache
        assert idx.match(self._toks(*range(8))) == pages[:1]
        # one token past: both pages hit
        assert idx.match(self._toks(*list(range(8)) + [99])) == pages
        # divergence mid-chain: only the common prefix page
        assert idx.match(self._toks(0, 1, 2, 3, 9, 9, 9, 9, 5)) == pages[:1]
        # sub-page prompts never match
        assert idx.match(self._toks(0, 1, 2)) == []

    def test_insert_adopts_refcount_and_first_writer_wins(self):
        pool, idx = PagePool(8), RadixPrefixIndex(page_size=4)
        a = pool.alloc(1)
        assert idx.insert(self._toks(*range(4)), a, pool) == 1
        assert pool.refcount(a[0]) == 2  # request + index
        b = pool.alloc(1)
        # same tokens computed again: the index keeps its page
        assert idx.insert(self._toks(*range(4)), b, pool) == 0
        assert pool.refcount(b[0]) == 1  # stays the request's alone
        assert idx.match(self._toks(*list(range(4)) + [7])) == a

    def test_insert_requires_page_alignment(self):
        pool, idx = PagePool(4), RadixPrefixIndex(page_size=4)
        with pytest.raises(ValueError, match="page-aligned"):
            idx.insert(self._toks(0, 1, 2), pool.alloc(1), pool)

    def test_evict_lru_leaves_first(self):
        pool, idx = PagePool(8), RadixPrefixIndex(page_size=2)
        chain = pool.alloc(2)  # one 2-page chain
        other = pool.alloc(1)  # one unrelated page
        idx.insert(self._toks(0, 1, 2, 3), chain, pool)
        idx.insert(self._toks(9, 9), other, pool)
        pool.decref(chain)
        pool.decref(other)  # requests retired; index holds everything
        idx.match(self._toks(9, 9, 5))  # touch `other`: now most recent
        # the chain is LRU: its leaf goes first, then (a leaf now) its
        # root — `other`, though a leaf all along, is more recent and
        # survives both evictions
        assert idx.evict(pool, 2) == 2
        assert idx.match(self._toks(0, 1, 2, 3, 5)) == []
        assert idx.match(self._toks(9, 9, 5)) == other

    def test_evict_never_touches_referenced_pages(self):
        pool, idx = PagePool(4), RadixPrefixIndex(page_size=2)
        busy = pool.alloc(1)  # still referenced by a "running request"
        idx.insert(self._toks(0, 1), busy, pool)
        assert idx.evict(pool, 1) == 0  # nothing evictable
        pool.decref(busy)
        assert idx.evict(pool, 1) == 1

    def test_len_counts_pages(self):
        pool, idx = PagePool(8), RadixPrefixIndex(page_size=2)
        idx.insert(self._toks(0, 1, 2, 3), pool.alloc(2), pool)
        assert len(idx) == 2


class TestPagedEngineIntegration:
    def test_no_kv_leakage_across_page_reuse(self):
        """The paged stale-row regression (kv_cache.py docstring): retire
        a LONG request, admit a SHORTER one whose pages land on the
        retired request's freed pages (prefix_cache off so retire frees
        them), and pin the new stream against a fresh engine's."""
        model = _llama()
        long_p, short_p = _prompts(3, (40, 6))
        engine = ServeEngine(
            model, num_slots=1, max_len=64, page_size=8,
            num_pages=8, prefix_cache=False,
        )
        engine.run([{"prompt": long_p, "max_new_tokens": 8}])
        assert engine.pool.in_use == 0  # all pages freed at retire
        got = engine.run([{"prompt": short_p, "max_new_tokens": 8}])[0]
        fresh = ServeEngine(
            model, num_slots=1, max_len=64, page_size=8,
            num_pages=8, prefix_cache=False,
        ).run([{"prompt": short_p, "max_new_tokens": 8}])[0]
        np.testing.assert_array_equal(got.tokens, fresh.tokens)

    def test_admission_gates_on_free_pages(self):
        """A pool with room for one request at a time serves a deeper
        queue FCFS: the page gate defers instead of over-admitting, and
        every stream stays exact."""
        model = _llama()
        prompts = _prompts(4, (10, 12, 9))
        reqs = [{"prompt": p, "max_new_tokens": 6} for p in prompts]
        # footprint per request: ceil((len + 6) / 8) <= 3 pages; 3 usable
        # pages => one request in flight at a time
        engine = ServeEngine(
            model, num_slots=3, max_len=64, page_size=8, num_pages=4,
            prefix_cache=False,
        )
        engine.submit(**reqs[0])
        engine.submit(**reqs[1])
        engine.step()
        assert engine.cache.active_count == 1  # second deferred on pages
        assert engine.scheduler.queue_depth == 1
        results = engine.run([dict(r) for r in reqs[2:]])
        baseline = ServeEngine(model, num_slots=3, max_len=64)
        base = baseline.run([dict(r) for r in reqs])
        np.testing.assert_array_equal(base[2].tokens, results[0].tokens)

    def test_eviction_under_pool_pressure_keeps_streams_exact(self):
        """Disjoint prompts churn through a small pool: the index must
        evict to admit, streams stay bit-identical to the slab engine,
        and the eviction counter records it."""
        model = _llama()
        prompts = _prompts(5, (17, 18, 19, 20))
        reqs = [{"prompt": p, "max_new_tokens": 5} for p in prompts]
        paged = ServeEngine(
            model, num_slots=2, max_len=64, page_size=8, num_pages=8
        )
        base = ServeEngine(model, num_slots=2, max_len=64)
        got = paged.run([dict(r) for r in reqs])
        want = base.run([dict(r) for r in reqs])
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        assert paged.metrics.counters["pages_evicted"] > 0

    def test_prefix_hit_skips_prefill_compute(self):
        """Second identical burst: warm prefill buckets shrink to the
        suffix, the hit-rate metrics show it, and pages-in-use high
        water stays within the pool."""
        model = _llama()
        rs = np.random.RandomState(7)
        shared = rs.randint(0, 256, (16,)).astype(np.int32)
        reqs = [
            {"prompt": np.concatenate(
                [shared, rs.randint(0, 256, (n,)).astype(np.int32)]),
             "max_new_tokens": 4}
            for n in (3, 5)
        ]
        engine = ServeEngine(
            model, num_slots=2, max_len=64, page_size=8
        )
        engine.run([dict(r) for r in reqs])
        cold = engine.metrics.counters["tokens_prefilled"]
        from torchdistx_tpu.serve.metrics import ServeMetrics

        engine.metrics = ServeMetrics(engine.num_slots, engine.num_pages)
        engine.run([dict(r) for r in reqs])
        snap = engine.metrics.snapshot()
        assert snap["tokens_prefilled"] < cold  # warm < cold, strictly
        assert snap["prefix_hit_tokens"] >= 16 * 2  # both shared prefixes
        assert 0 < snap["prefix_hit_rate"] <= 1
        assert snap["pages_in_use_hwm"] <= engine.pool.capacity

    def test_submit_rejects_unservable_footprint(self):
        engine = ServeEngine(
            _llama(), num_slots=1, max_len=64, page_size=8, num_pages=4
        )
        # 3 usable pages = 24 rows; 20 + 8 = 28 rows can never fit
        with pytest.raises(ValueError, match="allocatable pages"):
            engine.submit(np.zeros(20, np.int32), max_new_tokens=8)
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.submit(np.zeros(4, np.int32), max_new_tokens=0)
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.submit(np.zeros(4, np.int32), max_new_tokens=-3)

    def test_engine_rejects_bad_page_geometry(self):
        with pytest.raises(ValueError, match="multiple of page_size"):
            ServeEngine(_llama(), max_len=64, page_size=7)
        with pytest.raises(ValueError, match="num_pages requires"):
            ServeEngine(_llama(), max_len=64, num_pages=8)

    def test_retired_slot_tables_point_at_scratch(self):
        """After retire, the slot's whole table row names the scratch
        page — the fused chunk's frozen writes must never land in a page
        another request may now own."""
        engine = ServeEngine(
            _llama(), num_slots=1, max_len=64, page_size=8, decode_chunk=4
        )
        engine.run([{"prompt": _prompts(8, (9,))[0], "max_new_tokens": 5}])
        assert np.all(engine.cache.page_tables[0] == SCRATCH_PAGE)

    def test_metrics_to_json_schema(self):
        import json

        engine = ServeEngine(
            _llama(), num_slots=2, max_len=64, page_size=8
        )
        engine.run([{"prompt": _prompts(9, (6,))[0], "max_new_tokens": 3}])
        j = json.loads(json.dumps(engine.metrics.to_json()))
        assert set(j) == {
            "counters", "gauges", "histograms", "derived", "cycles",
        }
        assert j["counters"]["requests_completed"] == 1
        assert j["gauges"]["num_pages"] == engine.num_pages
        assert j["gauges"]["pages_in_use_hwm"] >= 1
        assert "prefix_hit_rate" in j["derived"]
        assert j["histograms"]["prefill_s"]["count"] == 1
        # snapshot() is a strict flattening of to_json()
        snap = engine.metrics.snapshot()
        for k, v in j["counters"].items():
            assert snap[k] == v
        assert snap["prefill_s_count"] == 1
