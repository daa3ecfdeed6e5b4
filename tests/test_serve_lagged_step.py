"""The serve step that reads its tokens one dispatch late (PR 35).

On the fused one-token program with whole prefills (``ServeEngine._lags``:
the slab and paged kinds of ``ENGINES``) ``step()`` issues decode dispatch
``D(k)`` while ``D(k-1)`` is still running and only then fetches and walks
``D(k-1)``'s block; the per-slot state ``D(k)`` starts from is ``D(k-1)``'s
final carry, kept on the device.  Pinned here:

- the streams are the sequential one-request reference's for every engine
  kind, with admissions into freed slots;
- a slot freed by one walk and admitted again does not receive the frozen
  token its previous tenant emitted in the dispatch that was in flight (the
  walk goes over the dispatch's own riders);
- EOS, which the host cannot foresee, is seen one step late and nothing is
  emitted after it; a deadline that expires with a dispatch in flight drops
  that dispatch's token and freezes the slot in the next;
- ``drain``, ``migrate_to`` and ``handoff_to`` settle first: the host
  mirrors are the device's carry, by value;
- ``lagged_dispatches``, ``lagged_slot_steps`` and ``masked_slot_steps`` of a
  scenario small enough to derive by hand, and 0 where the engine reads
  each dispatch at once;
- the phase histograms keep their arithmetic (one ``prefill_s`` record a
  prefill, inside ``schedule_s``);
- an idle engine has nothing in flight.
"""

import numpy as np
import pytest

from test_serve_dispatch_args import ENGINES, _engine, _llama, _reference

LAGGING = ("slab", "paged")


def _request(rs, n, new, i=0, sampled=False):
    return {
        "prompt": rs.randint(0, 256, (n,)).astype(np.int32),
        "max_new_tokens": new,
        "temperature": 0.8 if sampled else 0.0,
        "seed": 40 + i,
    }


def _run_to_end(engine, limit=200):
    for _ in range(limit):
        if not engine.step():
            return
    raise AssertionError("the engine did not drain")


def _carry(engine):
    """The device's per-slot state, by value: ``(tok, pos, ntok, fin)``."""
    c = np.asarray(engine._carry)
    return c[0], c[1], c[4], c[6]


def _assert_mirrors_are_the_carry(engine):
    """After a settle: for every running slot the host's last token,
    position and token count are what the device will start from."""
    assert engine._in_flight is None and not engine._pending_first
    tok, pos, ntok, fin = _carry(engine)
    running = engine.scheduler.running
    assert running  # the check has something to check
    for req in running:
        s = req.slot
        assert not fin[s]
        assert tok[s] == engine._last_tok[s] == req.generated[-1]
        assert pos[s] == engine.cache.pos[s]
        assert ntok[s] == engine._ntok[s] == len(req.generated)


@pytest.mark.parametrize("kind", list(ENGINES))
def test_streams_are_the_references_with_admissions_into_freed_slots(kind):
    """Seven requests on three slots, budgets 2 to 9: every slot is freed
    and taken again, greedy and sampled rows side by side."""
    model = _llama()
    rs = np.random.RandomState(35)
    requests = [
        _request(rs, n, new, i, sampled=i % 2 == 1)
        for i, (n, new) in enumerate(
            [(5, 9), (9, 2), (12, 4), (7, 1), (4, 6), (10, 3), (6, 5)]
        )
    ]
    engine = _engine(model, kind)
    assert engine._lags == (kind in LAGGING)
    handles = [engine.submit(**r) for r in requests[:4]]
    engine.step()
    handles += [engine.submit(**r) for r in requests[4:]]
    _run_to_end(engine)
    for request, handle in zip(requests, handles):
        result = handle.result()
        assert result.finish_reason == "length"
        np.testing.assert_array_equal(result.tokens, _reference(model, request))
    assert engine._in_flight is None


@pytest.mark.parametrize("kind", LAGGING)
def test_a_readmitted_slot_gets_none_of_its_previous_tenants_tokens(kind):
    """``old`` finishes inside ``D(k-1)``; ``D(k)``, issued before the host
    walked ``D(k-1)``, carries it frozen and holds its last token again at
    its slot.  ``new`` takes the slot in the next step, and the walk of
    ``D(k)`` comes after that admission: over ``scheduler.running`` it would
    hand ``new`` that token as its second one."""
    model = _llama()
    rs = np.random.RandomState(5)
    long_, old, new = (
        _request(rs, n, m, i) for i, (n, m) in enumerate([(6, 14), (8, 3), (7, 5)])
    )
    engine = _engine(model, kind, num_slots=2)
    h_long, h_old, h_new = (engine.submit(**r) for r in (long_, old, new))
    while not h_old.done():
        engine.step()
    # the finish was seen with the successor already in flight, ``old``
    # among its riders, and its block repeats ``old``'s last token there
    flight = engine._in_flight
    assert flight is not None
    slot = next(s for req, s in flight.riders if req is h_old._request)
    last = h_old.result().tokens[-1]
    assert np.asarray(flight.outputs[0])[0, slot] == last
    engine.step()  # admits ``new`` into that slot, then walks that block
    assert h_new._request.slot == slot
    assert h_new._request.generated == [_reference(model, new)[0]]
    _run_to_end(engine)
    for request, handle in ((long_, h_long), (old, h_old), (new, h_new)):
        np.testing.assert_array_equal(
            handle.result().tokens, _reference(model, request)
        )


@pytest.mark.parametrize("kind", LAGGING)
def test_an_eos_the_device_froze_on_is_seen_one_step_late(kind):
    model = _llama()
    rs = np.random.RandomState(11)
    stopper, other = _request(rs, 7, 12, 0), _request(rs, 5, 12, 1)
    ref = _reference(model, stopper)
    # the first token of the stream that none before it equals, past the
    # second: the engine's EOS
    at = next(i for i in range(2, len(ref)) if ref[i] not in ref[:i])
    eos = int(ref[at])
    other_ref = _reference(model, other)
    other_end = next(
        (i for i, t in enumerate(other_ref) if t == eos), len(other_ref) - 1
    )
    engine = _engine(model, kind, num_slots=2, eos_token=eos)
    h_stop, h_other = engine.submit(**stopper), engine.submit(**other)
    steps = 0
    while not h_stop.done():
        engine.step()
        steps += 1
    # token i is visible at the end of step i + 1 (the first at the end of
    # step 1): the EOS at index ``at`` one step after the parent's ``at``
    assert steps == at + 1
    result = h_stop.result()
    assert result.finish_reason == "stop"
    np.testing.assert_array_equal(result.tokens, ref[: at + 1])
    if other_end > at:
        # the successor was issued before the EOS was seen: frozen there
        assert engine.metrics.counters["lagged_slot_steps"] == 1
    _run_to_end(engine)
    np.testing.assert_array_equal(
        h_other.result().tokens, other_ref[: other_end + 1]
    )
    np.testing.assert_array_equal(h_stop.result().tokens, ref[: at + 1])


@pytest.mark.parametrize("kind", LAGGING)
def test_a_deadline_that_expires_with_a_dispatch_in_flight(kind):
    model = _llama()
    rs = np.random.RandomState(3)
    late, steady, after = (
        _request(rs, n, m, i) for i, (n, m) in enumerate([(6, 12), (9, 12), (5, 4)])
    )
    engine = _engine(model, kind, num_slots=2)
    h_late, h_steady = engine.submit(**late), engine.submit(**steady)
    for _ in range(3):
        engine.step()
    had = list(h_late._request.generated)
    slot = h_late._request.slot
    assert len(had) == 3 and engine._in_flight is not None
    h_late._request.deadline_s = 0.0  # expired at the next schedule
    h_after = engine.submit(**after)
    engine.step()
    # finished with the tokens it had: the token of the dispatch that was
    # in flight is dropped, and no device rule had frozen the slot
    result = h_late.result()
    assert result.finish_reason == "deadline" and result.truncated
    assert result.tokens.tolist() == had == _reference(model, late)[:3].tolist()
    assert engine.metrics.counters["lagged_slot_steps"] == 0
    # the slot went to ``after`` in the same step, from the host's column
    assert h_after._request.slot == slot
    _run_to_end(engine)
    for request, handle in ((steady, h_steady), (after, h_after)):
        np.testing.assert_array_equal(
            handle.result().tokens, _reference(model, request)
        )


@pytest.mark.parametrize("kind", LAGGING)
def test_an_expired_slot_is_carried_as_finished(kind):
    """No one takes the slot: the next dispatch starts it from the host's
    column, finished, and its carry says so."""
    model = _llama()
    rs = np.random.RandomState(4)
    late, steady = _request(rs, 6, 12, 0), _request(rs, 9, 12, 1)
    engine = _engine(model, kind, num_slots=2)
    h_late, h_steady = engine.submit(**late), engine.submit(**steady)
    for _ in range(3):
        engine.step()
    slot = h_late._request.slot
    assert not _carry(engine)[3][slot]
    h_late._request.deadline_s = 0.0
    engine.step()
    assert h_late.result().finish_reason == "deadline"
    assert _carry(engine)[3][slot] == 1
    _run_to_end(engine)
    np.testing.assert_array_equal(
        h_steady.result().tokens, _reference(model, steady)
    )


@pytest.mark.parametrize("kind", LAGGING)
@pytest.mark.parametrize("how", ["drain", "migrate_to", "handoff_to"])
def test_moves_and_drains_settle_first(kind, how, monkeypatch):
    model = _llama()
    rs = np.random.RandomState(8)
    requests = [_request(rs, n, 10, i, sampled=i == 1)
                for i, n in enumerate((6, 9))]
    engine = _engine(model, kind, num_slots=2)
    target = _engine(model, kind, num_slots=2)
    handles = [engine.submit(**r) for r in requests]
    for _ in range(4):
        engine.step()
    assert engine._in_flight is not None
    if how == "drain":
        engine.drain()
        _assert_mirrors_are_the_carry(engine)
        engine.drain(complete=True)
    else:
        copy = "_copy_kv_pages" if engine.paged else "_copy_kv_slot"
        real = getattr(engine, copy)
        seen = []

        def checked(*a, **k):
            # at the moment the cache is read, the source is settled
            _assert_mirrors_are_the_carry(engine)
            seen.append(1)
            return real(*a, **k)

        monkeypatch.setattr(engine, copy, checked)
        if how == "migrate_to":
            engine.migrate_to(target)
            assert len(seen) == 2
        else:
            engine.handoff_to(target, handles[0]._request)
            assert len(seen) == 1
            _run_to_end(engine)
        _run_to_end(target)
    for request, handle in zip(requests, handles):
        np.testing.assert_array_equal(
            handle.result().tokens, _reference(model, request)
        )


def _two_requests():
    rs = np.random.RandomState(2)
    return [_request(rs, 6, 3, 0), _request(rs, 8, 6, 1)]


@pytest.mark.parametrize("kind", LAGGING)
def test_counters_of_a_scenario_derived_by_hand(kind):
    """Two slots, budgets 3 (``a``) and 6 (``c``), both admitted in step 1.

    ====  ========================  ==================================
    step  issued                    fetched and walked
    ====  ========================  ==================================
    1     two prefills, ``D1``      the two first tokens
    2     ``D2`` behind ``D1``      ``D1``: token 2 of each
    3     ``D3`` behind ``D2``      ``D2``: ``a`` ends (3 of 3), seen
                                    with ``D3`` in flight, which
                                    carries it frozen; token 3 of ``c``
    4     ``D4`` behind ``D3``      ``D3``: ``a`` skipped; token 4
    5     ``D5`` behind ``D4``      ``D4``: token 5
    6     nothing: ``c`` has 5 of   ``D5``: token 6, ``c`` ends with
          6 and rides ``D5``        nothing in flight
    ====  ========================  ==================================
    """
    model = _llama()
    requests = _two_requests()
    engine = _engine(model, kind, num_slots=2)
    handles = [engine.submit(**r) for r in requests]
    engine.step()
    # a step that admitted returns with the first tokens on the host
    assert [len(h._request.generated) for h in handles] == [1, 1]
    assert engine.metrics.counters["lagged_dispatches"] == 0
    steps = 1
    while engine.step():
        steps += 1
    assert steps + 1 == 6
    got = {k: v for k, v in engine.metrics.counters.items() if v}
    for name in ("prefix_lookup_tokens",):  # the paged kind's own
        got.pop(name, None)
    assert got == {
        "requests_submitted": 2, "requests_admitted": 2,
        "requests_completed": 2, "prefill_calls": 2, "tokens_prefilled": 32,
        "tokens_generated": 9, "tokens_decoded": 7,
        "decode_dispatches": 5, "decode_steps": 5,
        "host_syncs": 2 + 5,  # a fetch the host waits on: as before
        "lagged_dispatches": 4,  # D2 .. D5 had an unread predecessor
        "lagged_slot_steps": 1, "masked_slot_steps": 1,  # ``a`` in D3
    }
    assert engine._in_flight is None
    for request, handle in zip(requests, handles):
        np.testing.assert_array_equal(
            handle.result().tokens, _reference(model, request)
        )


@pytest.mark.parametrize(
    "kind,opts",
    [
        ("persistent", {}),
        ("speculative", {}),
        ("persistent-speculative-paged", {}),
        ("slab", {"prefill_buckets": (8, 16), "chunked_prefill": 8}),
        ("paged", {"prefill_buckets": (8, 16), "chunked_prefill": 8}),
    ],
    ids=["persistent", "speculative", "persistent-speculative-paged",
         "slab-chunked-prefill", "paged-chunked-prefill"],
)
def test_engines_that_settle_at_once_never_lag(kind, opts):
    model = _llama()
    requests = _two_requests()
    engine = _engine(model, kind, num_slots=2, **opts)
    assert not engine._lags
    handles = [engine.submit(**r) for r in requests]
    while engine.step():
        assert engine._in_flight is None and (
            engine._persistent or not engine._pending_first
        )
    counters = engine.metrics.counters
    assert counters["lagged_dispatches"] == counters["lagged_slot_steps"] == 0
    for request, handle in zip(requests, handles):
        np.testing.assert_array_equal(
            handle.result().tokens, _reference(model, request)
        )


@pytest.mark.parametrize("kind", LAGGING)
def test_phase_histograms_keep_their_arithmetic(kind):
    """One ``prefill_s`` record a prefill, each inside ``serve/schedule``
    (dispatch and wait both), so the host's own scheduling time, their
    difference, is not negative; a decode record a dispatch."""
    model = _llama()
    rs = np.random.RandomState(9)
    requests = [_request(rs, 4 + i, 3 + i, i) for i in range(5)]
    engine = _engine(model, kind, num_slots=2)
    engine.run(requests)
    m = engine.metrics
    assert m.prefill_s.count == m.counters["prefill_calls"] == 5
    assert m.schedule_s.total >= m.prefill_s.total > 0
    assert m.decode_s.count >= m.counters["decode_dispatches"]
    assert m.decode_args_s.count == m.counters["decode_dispatches"]


@pytest.mark.parametrize("eos", [False, True], ids=["budget", "eos"])
@pytest.mark.parametrize("kind", LAGGING)
def test_an_idle_engine_has_nothing_in_flight(kind, eos):
    """By budget the host foresees the last dispatch and issues no other;
    an EOS it cannot foresee, so ``run`` settles the successor."""
    model = _llama()
    rs = np.random.RandomState(6)
    request = _request(rs, 6, 8)
    ref = _reference(model, request)
    opts = {}
    if eos:
        at = next(i for i in range(2, len(ref)) if ref[i] not in ref[:i])
        opts["eos_token"] = int(ref[at])
        ref = ref[: at + 1]
    engine = _engine(model, kind, num_slots=2, **opts)
    (result,) = engine.run([request])
    np.testing.assert_array_equal(result.tokens, ref)
    assert engine._in_flight is None and not engine._pending_first
    # and again, from rest: the first dispatch has no predecessor
    before = engine.metrics.counters["lagged_dispatches"]
    (result,) = engine.run([request])
    np.testing.assert_array_equal(result.tokens, ref)
    assert engine._in_flight is None
    dispatches = engine.metrics.counters["decode_dispatches"]
    assert engine.metrics.counters["lagged_dispatches"] - before == (
        dispatches // 2 - 1
    )
