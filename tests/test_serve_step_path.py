"""The serve step path against the records of its parent (PR 32).

``ServeEngine`` has one decode step and one prefill dispatch; until PR 32
it had four of each.  What the merge had to keep to the letter is pinned
here, for each of the five engine kinds of
``test_serve_dispatch_args.ENGINES`` with ``chunked_prefill`` off and on:

- the token streams are those of the sequential one-request reference
  (``forward_cached`` and the slot sampler alone);
- the whole counter dict of ``engine.metrics`` and every request's
  lifecycle events (names and fields, timestamps left out) are the
  literal values of ``GOLDEN`` below.

``GOLDEN`` was captured from commit ``0389ff8`` (PR 31, the parent of the
merge, with its four decode steps and four prefill dispatchers) by running
this file as a script there: ``PYTHONPATH=.
JAX_PLATFORMS=cpu python tests/test_serve_step_path.py`` prints both dicts.  It is not to be
regenerated to make a failure go away: a differing counter or event is a
copy merged wrongly (a skipped ``active`` check, a lost
``ring_full_drains``, a ``prefill_chunk`` event on an unchunked prefill).

**The one sanctioned regeneration (PR 35)**: ``slab-whole`` and
``paged-whole``, the two cases whose engine now reads its tokens one
dispatch late (``ServeEngine._lags``: the fused one-token program with
whole prefills), because there the order of a step itself changed.  Six
counters changed and no event of any request (a request's own order
``first_token`` / ``decode_chunk`` x n / ``finish`` is what it was; only
the step in which each lands is one later).  From the parent's numbers:

- ``decode_dispatches`` / ``decode_steps`` 21 -> 22.  The run ends with
  request 3 (budget 19), which takes slot 1 after request 1 (budget 4).
  The parent saw request 1 finish in the walk of ``D3`` and admitted
  request 3 in step 4: tokens 2..19 in ``D4..D21``.  Here the walk of
  ``D3`` is in step 4, the admission in step 5: ``D5..D22``.  The host
  foresees that last budget, so no ``D23`` is issued.
- ``host_syncs`` 27 -> 28: a fetch a decode dispatch and one a prefill, as
  before, 22 + 6.
- ``lagged_dispatches`` 21: every dispatch but ``D1`` was issued with its
  predecessor unread.
- ``lagged_slot_steps`` 5, ``masked_slot_steps`` 0 -> 5: requests 0, 1, 2,
  4 and 5 end by their budget inside a dispatch whose successor is already
  in flight with the slot frozen in it (one slot-step each,
  ``decode_chunk`` 1); request 3 ends the run with nothing in flight.

The chunked cases of those kinds (``chunked_prefill`` settles at once),
the persistent and the speculative kinds are the parent's to the letter.

**What PR 38 changed in how ``GOLDEN`` is read, not in ``GOLDEN``.**  The
engine no longer appends a ``decode_chunk`` event a request a tick: a
request keeps the running numbers of its first and last decode dispatch
(``first_decode_cycle`` / ``last_decode_cycle``, also in its ``finish``
event).  ``GOLDEN`` still holds the parent's ``decode_chunk{tokens=n}``
entries; the comparison takes them out of the expected lifecycle and
holds the two integers to them instead: the blocks that gave the request
a token (``tokens`` > 0) are consecutive dispatches, so there are ``last -
first + 1`` of them.

No combination is refused by the constructor: all ten run.  The scenario
has prompts on both sides of the chunk threshold, one prompt that shares
its first page with an earlier one (the paged engines' warm prefill and
prefix counters), and admissions mid-run.  ``FOLDED`` adds the chunked
way that ends in ONE chunk (``_prefill_chunks`` folds a tail whose bucket
would overrun ``max_len``): it still parks, counts and logs as chunked.
"""

import numpy as np
import pytest

from test_serve_dispatch_args import (
    ENGINES,
    _engine,
    _llama,
    _reference,
)

BUCKETS = (8, 16)
CHUNK = 8  # a bucket: prompts of 9, 11 and 13 tokens take the chunked way


def _requests():
    rs = np.random.RandomState(32)
    lengths = (6, 11, 9, 4, 13, 8)
    out = [
        {
            "prompt": rs.randint(0, 256, (n,)).astype(np.int32),
            # finishes spread over the steps; the greedy rows run long
            # enough to repeat themselves, so that drafts are accepted
            "max_new_tokens": 16 + i if i % 3 == 0 else 3 + i,
            "temperature": 0.0 if i % 3 == 0 else 0.7 + 0.1 * i,
            "seed": 100 + i,
        }
        for i, n in enumerate(lengths)
    ]
    # the fifth prompt opens with the second's first page (page_size 8)
    out[4]["prompt"][:8] = out[1]["prompt"][:8]
    return out


#: a ``finish`` event's fields that say WHICH dispatches, compared apart
_CYCLE_FIELDS = ("first_cycle", "last_cycle")


def _events(result):
    """A request's lifecycle as strings, ``name{field=value,...}``, runs
    of one string folded to ``string*n``; timestamps left out."""
    flat = []
    for name, _, data in result.events:
        data = {k: v for k, v in (data or {}).items()
                if k != "ts" and k not in _CYCLE_FIELDS}
        fields = ",".join(f"{k}={data[k]}" for k in sorted(data))
        flat.append(name + (f"{{{fields}}}" if fields else ""))
    out = []
    for s in flat:
        if out and out[-1][0] == s:
            out[-1][1] += 1
        else:
            out.append([s, 1])
    return [s if n == 1 else f"{s}*{n}" for s, n in out]


def _serve(engine, requests, first=2):
    """``first`` requests, two steps, then the rest while those decode:
    ``(token streams, non-zero counters, events)``."""
    handles = [engine.submit(**r) for r in requests[:first]]
    if first < len(requests):
        engine.step()
        engine.step()
        handles += [engine.submit(**r) for r in requests[first:]]
    while engine.step():
        pass
    results = [h.result() for h in handles]
    counters = {k: int(v) for k, v in engine.metrics.counters.items() if v}
    return results, counters, [_events(r) for r in results]


def _golden_lifecycle(events):
    """A request's ``GOLDEN`` events as the engine logs them since PR 38
    (no ``decode_chunk`` entry), and how many of those entries held a
    token: the decode dispatches from its first to its last."""
    kept, blocks = [], 0
    for entry in events:
        if not entry.startswith("decode_chunk"):
            kept.append(entry)
            continue
        fields, _, runs = entry.partition("*")
        if fields != "decode_chunk{tokens=0}":
            blocks += int(runs or 1)
    return kept, blocks


def _assert_lifecycles(results, events, golden):
    for result, logged, expected in zip(results, events, golden):
        kept, blocks = _golden_lifecycle(expected)
        assert logged == kept
        first, last = result.first_decode_cycle, result.last_decode_cycle
        assert (0 if first is None else last - first + 1) == blocks
        assert result.events[-1][2]["first_cycle"] == first
        assert result.events[-1][2]["last_cycle"] == last


def _serve_kind(model, kind, chunked):
    """``whole`` is the step the serve cells run (one token a dispatch,
    one prefill a prompt); ``chunked`` chunks the prefill and fuses two
    decode steps a dispatch, so that a finish inside a chunk masks a
    slot-step (the persistent kinds ignore ``decode_chunk``)."""
    engine = _engine(
        model, kind, prefill_buckets=BUCKETS, chunked_prefill=chunked,
        decode_chunk=2 if chunked else 1,
    )
    return _serve(engine, _requests())


def _serve_folded(model, kind):
    """``max_len=12``, buckets ``(8, 12)``, threshold 8, prompts of 9 and
    8 tokens: the 9's split ``(0, 8), (8, 1)`` would pad its tail to ``8 +
    8 > 12``, so it folds to ``(0, 9)``, one chunk."""
    rs = np.random.RandomState(7)
    requests = [
        {"prompt": rs.randint(0, 256, (n,)).astype(np.int32),
         "max_new_tokens": 3, "temperature": 0.0, "seed": 1}
        for n in (9, 8)
    ]
    opts = dict(max_len=12, prefill_buckets=(8, 12), chunked_prefill=8)
    if kind == "paged":
        opts["page_size"] = 4
    return _serve(_engine(model, kind, **opts), requests, first=2)


def _case(kind, chunked):
    return f"{kind}-{'chunked' if chunked else 'whole'}"


@pytest.mark.parametrize("chunked", [None, CHUNK], ids=["whole", "chunked"])
@pytest.mark.parametrize("kind", list(ENGINES))
def test_step_path_counters_events_and_streams_are_the_parents(kind, chunked):
    model = _llama()
    results, counters, events = _serve_kind(model, kind, chunked)
    for request, served in zip(_requests(), results):
        np.testing.assert_array_equal(served.tokens, _reference(model, request))
    golden = GOLDEN[_case(kind, chunked)]
    assert counters == golden["counters"]
    _assert_lifecycles(results, events, golden["events"])


@pytest.mark.parametrize("kind", ["slab", "paged"])
def test_a_chunked_prefill_folded_to_one_chunk_is_still_chunked(kind):
    """One chunk, through the chunked way: parked, ``chunked_prefills``
    and ``prefill_chunks`` counted, ``chunks=1`` and a ``prefill_chunk``
    event logged.  The prompt of 8 beside it is at the threshold, not
    over it: the whole way, none of those."""
    results, counters, events = _serve_folded(_llama(), kind)
    assert counters == FOLDED[kind]["counters"]
    _assert_lifecycles(results, events, FOLDED[kind]["events"])


GOLDEN = {
    "slab-whole": {
        "counters": {
            "decode_dispatches": 22, "decode_steps": 22, "host_syncs": 28,
            "lagged_dispatches": 21, "lagged_slot_steps": 5, "masked_slot_steps": 5,
            "prefill_calls": 6, "requests_admitted": 6, "requests_completed": 6,
            "requests_submitted": 6, "tokens_decoded": 53, "tokens_generated": 59,
            "tokens_prefilled": 72,
        },
        "events": [
            [
                "submit", "admitted{slot=0}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=1}*15", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=16,cold=True}",
                "first_token", "decode_chunk{tokens=1}*3", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}", "prefill{bucket=16,cold=True}",
                "first_token", "decode_chunk{tokens=1}*4", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=1}*18", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}", "prefill{bucket=16,cold=True}",
                "first_token", "decode_chunk{tokens=1}*6", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=1}*7", "finish{reason=length}",
            ],
        ],
    },
    "slab-chunked": {
        "counters": {
            "chunked_prefills": 3, "decode_dispatches": 13, "decode_steps": 26,
            "host_syncs": 19, "masked_slot_steps": 3, "prefill_calls": 6,
            "prefill_chunks": 6, "prefill_interleaved_dispatches": 3,
            "requests_admitted": 6, "requests_completed": 6, "requests_submitted": 6,
            "tokens_decoded": 53, "tokens_generated": 59, "tokens_prefilled": 72,
        },
        "events": [
            [
                "submit", "admitted{slot=0}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=2}*7", "decode_chunk{tokens=1}",
                "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=8,chunks=2,cold=True}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=2}", "decode_chunk{tokens=1}",
                "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=8,chunks=2,cold=True}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=2}*2", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=2}*9", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=8,chunks=2,cold=True}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=2}*3", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=0}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=2}*3", "decode_chunk{tokens=1}",
                "finish{reason=length}",
            ],
        ],
    },
    "paged-whole": {
        "counters": {
            "decode_dispatches": 22, "decode_steps": 22, "host_syncs": 28,
            "lagged_dispatches": 21, "lagged_slot_steps": 5, "masked_slot_steps": 5,
            "prefill_calls": 6, "prefix_hit_tokens": 8, "prefix_lookup_tokens": 51,
            "requests_admitted": 6, "requests_completed": 6, "requests_submitted": 6,
            "tokens_decoded": 53, "tokens_generated": 59, "tokens_prefilled": 64,
        },
        "events": [
            [
                "submit", "admitted{slot=0}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=1}*15", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}",
                "prefill{bucket=16,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=1}*3", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}",
                "prefill{bucket=16,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=1}*4", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=1}*18", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}",
                "prefill{bucket=8,cold=False,prefix_hit_tokens=8}", "first_token",
                "decode_chunk{tokens=1}*6", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=1}*7", "finish{reason=length}",
            ],
        ],
    },
    "paged-chunked": {
        "counters": {
            "chunked_prefills": 2, "decode_dispatches": 13, "decode_steps": 26,
            "host_syncs": 19, "masked_slot_steps": 3, "prefill_calls": 6,
            "prefill_chunks": 4, "prefill_interleaved_dispatches": 2,
            "prefix_hit_tokens": 8, "prefix_lookup_tokens": 51, "requests_admitted": 6,
            "requests_completed": 6, "requests_submitted": 6, "tokens_decoded": 53,
            "tokens_generated": 59, "tokens_prefilled": 64,
        },
        "events": [
            [
                "submit", "admitted{slot=0}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=2}*7", "decode_chunk{tokens=1}",
                "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}",
                "prefill{bucket=8,chunks=2,cold=True,prefix_hit_tokens=0}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=2}", "decode_chunk{tokens=1}",
                "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}",
                "prefill{bucket=8,chunks=2,cold=True,prefix_hit_tokens=0}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=2}*2", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=2}*9", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}",
                "prefill{bucket=8,cold=False,prefix_hit_tokens=8}", "first_token",
                "decode_chunk{tokens=2}*3", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=0}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=2}*3", "decode_chunk{tokens=1}",
                "finish{reason=length}",
            ],
        ],
    },
    "persistent-whole": {
        "counters": {
            "decode_dispatches": 3, "decode_steps": 40, "host_syncs": 3,
            "loop_iterations": 40, "masked_slot_steps": 38, "prefill_calls": 6,
            "requests_admitted": 6, "requests_completed": 6, "requests_submitted": 6,
            "ring_drains": 3, "tokens_decoded": 53, "tokens_generated": 59,
            "tokens_prefilled": 72,
        },
        "events": [
            [
                "submit", "admitted{slot=0}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=15}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=16,cold=True}",
                "first_token", "decode_chunk{tokens=3}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=0}", "prefill{bucket=16,cold=True}",
                "first_token", "decode_chunk{tokens=4}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=18}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}", "prefill{bucket=16,cold=True}",
                "first_token", "decode_chunk{tokens=6}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=0}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=7}", "finish{reason=length}",
            ],
        ],
    },
    "persistent-chunked": {
        "counters": {
            "chunked_prefills": 3, "decode_dispatches": 6, "decode_steps": 49,
            "host_syncs": 6, "loop_iterations": 49, "masked_slot_steps": 14,
            "prefill_calls": 6, "prefill_chunks": 6, "prefill_interleaved_dispatches":
            3, "requests_admitted": 6, "requests_completed": 6, "requests_submitted": 6,
            "ring_drains": 6, "ring_full_drains": 1, "tokens_decoded": 53,
            "tokens_generated": 59, "tokens_prefilled": 72,
        },
        "events": [
            [
                "submit", "admitted{slot=0}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=15}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=8,chunks=2,cold=True}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=3}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=0}", "prefill{bucket=8,chunks=2,cold=True}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=4}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "decode_chunk{tokens=0}",
                "prefill{bucket=8,cold=True}", "first_token", "decode_chunk{tokens=18}",
                "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}", "decode_chunk{tokens=0}",
                "prefill{bucket=8,chunks=2,cold=True}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=6}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=0}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=7}", "finish{reason=length}",
            ],
        ],
    },
    "speculative-whole": {
        "counters": {
            "decode_dispatches": 19, "decode_steps": 19, "draft_tokens_accepted": 6,
            "draft_tokens_proposed": 94, "host_syncs": 25, "prefill_calls": 6,
            "requests_admitted": 6, "requests_completed": 6, "requests_submitted": 6,
            "spec_rejected_lane_steps": 88, "tokens_decoded": 53, "tokens_generated":
            59, "tokens_prefilled": 72,
        },
        "events": [
            [
                "submit", "admitted{slot=0}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=1}*11", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=16,cold=True}",
                "first_token", "decode_chunk{tokens=1}*3", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}", "prefill{bucket=16,cold=True}",
                "first_token", "decode_chunk{tokens=1}*4", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=1}*16", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}", "prefill{bucket=16,cold=True}",
                "first_token", "decode_chunk{tokens=1}*6", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=0}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=1}*7", "finish{reason=length}",
            ],
        ],
    },
    "speculative-chunked": {
        "counters": {
            "chunked_prefills": 3, "decode_dispatches": 12, "decode_steps": 24,
            "draft_tokens_accepted": 6, "draft_tokens_proposed": 94, "host_syncs": 18,
            "masked_slot_steps": 3, "prefill_calls": 6, "prefill_chunks": 6,
            "prefill_interleaved_dispatches": 3, "requests_admitted": 6,
            "requests_completed": 6, "requests_submitted": 6,
            "spec_rejected_lane_steps": 88, "tokens_decoded": 53, "tokens_generated":
            59, "tokens_prefilled": 72,
        },
        "events": [
            [
                "submit", "admitted{slot=0}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=2}*5", "decode_chunk{tokens=1}",
                "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=8,chunks=2,cold=True}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=2}", "decode_chunk{tokens=1}",
                "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=8,chunks=2,cold=True}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=2}*2", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=2}*8", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=0}", "prefill{bucket=8,chunks=2,cold=True}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=2}*3", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=2}*3", "decode_chunk{tokens=1}",
                "finish{reason=length}",
            ],
        ],
    },
    "persistent-speculative-paged-whole": {
        "counters": {
            "decode_dispatches": 3, "decode_steps": 34, "draft_tokens_accepted": 6,
            "draft_tokens_proposed": 94, "host_syncs": 3, "loop_iterations": 34,
            "masked_slot_steps": 30, "prefill_calls": 6, "prefix_hit_tokens": 8,
            "prefix_lookup_tokens": 51, "requests_admitted": 6, "requests_completed": 6,
            "requests_submitted": 6, "ring_drains": 3, "spec_rejected_lane_steps": 88,
            "tokens_decoded": 53, "tokens_generated": 59, "tokens_prefilled": 64,
        },
        "events": [
            [
                "submit", "admitted{slot=0}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=11}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}",
                "prefill{bucket=16,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=3}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=0}",
                "prefill{bucket=16,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=4}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=16}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}",
                "prefill{bucket=8,cold=False,prefix_hit_tokens=8}", "first_token",
                "decode_chunk{tokens=6}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=0}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=7}", "finish{reason=length}",
            ],
        ],
    },
    "persistent-speculative-paged-chunked": {
        "counters": {
            "chunked_prefills": 2, "decode_dispatches": 5, "decode_steps": 37,
            "draft_tokens_accepted": 6, "draft_tokens_proposed": 94, "host_syncs": 5,
            "loop_iterations": 37, "masked_slot_steps": 22, "prefill_calls": 6,
            "prefill_chunks": 4, "prefill_interleaved_dispatches": 2,
            "prefix_hit_tokens": 8, "prefix_lookup_tokens": 51, "requests_admitted": 6,
            "requests_completed": 6, "requests_submitted": 6, "ring_drains": 5,
            "ring_full_drains": 1, "spec_rejected_lane_steps": 88, "tokens_decoded": 53,
            "tokens_generated": 59, "tokens_prefilled": 64,
        },
        "events": [
            [
                "submit", "admitted{slot=0}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=11}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}",
                "prefill{bucket=8,chunks=2,cold=True,prefix_hit_tokens=0}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=3}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=0}",
                "prefill{bucket=8,chunks=2,cold=True,prefix_hit_tokens=0}",
                "prefill_chunk{bucket=8,start=0}", "prefill_chunk{bucket=8,start=8}",
                "first_token", "decode_chunk{tokens=4}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "decode_chunk{tokens=0}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=16}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=2}", "decode_chunk{tokens=0}",
                "prefill{bucket=8,cold=False,prefix_hit_tokens=8}", "first_token",
                "decode_chunk{tokens=6}", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=0}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=7}", "finish{reason=length}",
            ],
        ],
    },
}

FOLDED = {
    "slab": {
        "counters": {
            "chunked_prefills": 1, "decode_dispatches": 2, "decode_steps": 2,
            "host_syncs": 4, "prefill_calls": 2, "prefill_chunks": 1,
            "requests_admitted": 2, "requests_completed": 2, "requests_submitted": 2,
            "tokens_decoded": 4, "tokens_generated": 6, "tokens_prefilled": 20,
        },
        "events": [
            [
                "submit", "admitted{slot=0}", "prefill{bucket=12,chunks=1,cold=True}",
                "prefill_chunk{bucket=12,start=0}", "first_token",
                "decode_chunk{tokens=1}*2", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}", "prefill{bucket=8,cold=True}",
                "first_token", "decode_chunk{tokens=1}*2", "finish{reason=length}",
            ],
        ],
    },
    "paged": {
        "counters": {
            "chunked_prefills": 1, "decode_dispatches": 2, "decode_steps": 2,
            "host_syncs": 4, "prefill_calls": 2, "prefill_chunks": 1,
            "prefix_lookup_tokens": 17, "requests_admitted": 2, "requests_completed": 2,
            "requests_submitted": 2, "tokens_decoded": 4, "tokens_generated": 6,
            "tokens_prefilled": 20,
        },
        "events": [
            [
                "submit", "admitted{slot=0}",
                "prefill{bucket=12,chunks=1,cold=True,prefix_hit_tokens=0}",
                "prefill_chunk{bucket=12,start=0}", "first_token",
                "decode_chunk{tokens=1}*2", "finish{reason=length}",
            ],
            [
                "submit", "admitted{slot=1}",
                "prefill{bucket=8,cold=True,prefix_hit_tokens=0}", "first_token",
                "decode_chunk{tokens=1}*2", "finish{reason=length}",
            ],
        ],
    },
}


if __name__ == "__main__":  # the capture (see the module docstring)
    import textwrap

    def wrapped(items, open_, close, indent):
        pad = " " * indent
        body = textwrap.fill(
            ", ".join(items), width=88, initial_indent=pad + " " * 4,
            subsequent_indent=pad + " " * 4, break_long_words=False,
            break_on_hyphens=False,
        )
        return f"{open_}\n{body},\n{pad}{close}"

    def show(name, cases):
        print(f"{name} = {{")
        for case, (_, counters, events) in cases.items():
            print(f'    "{case}": {{')
            pairs = [f'"{k}": {v}' for k, v in sorted(counters.items())]
            print(f'        "counters": {wrapped(pairs, "{", "}", 8)},')
            print('        "events": [')
            for ev in events:
                quoted = [f'"{e}"' for e in ev]
                print(f'            {wrapped(quoted, "[", "]", 12)},')
            print("        ],\n    },")
        print("}")

    show("GOLDEN", {
        _case(k, c): _serve_kind(_llama(), k, c)
        for k in ENGINES for c in (None, CHUNK)
    })
    show("FOLDED", {k: _serve_folded(_llama(), k) for k in ("slab", "paged")})
