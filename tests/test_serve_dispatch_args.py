"""How a serve dispatch's small arguments cross to the device (PR 31).

The step path hands its programs HOST arrays and the dispatch call makes
the transfers: the per-slot state of a decode dispatch is one packed
``(7, num_slots)`` int32 array (``generation.pack_slot_state``; on the
fused one-token program ``(8, num_slots)``: the row that says which slots
start from it, PR 35), a prefill's scalars are NumPy scalars and
one-element arrays with their dtypes written out.  What else a program
takes is already on the device and travels from one program to the next:
the first tokens' vector (every prefill, the fused one-token decode) and
that decode's carry.  Pinned here:

- no ``jnp.asarray`` / ``jax.device_put`` between the start of
  ``serve/decode_args`` (or of ``serve/schedule``) and the dispatch — the
  persistent loop's first-token splice excepted, and counted — and the
  programs' small arguments ARE host arrays;
- the token streams are those of a sequential one-request reference
  built from ``forward_cached`` and the slot sampler alone, greedy and
  sampled, with requests admitted mid-run;
- every program compiles once (no argument became weakly typed);
- nothing of the engine's live mirrors is aliased by a dispatch;
- pack / unpack round-trip bit for bit.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchdistx_tpu as tdx
from torchdistx_tpu.generation import (
    SLOT_STATE_ROWS,
    _make_slot_sampler,
    _unpack_slot_state,
    pack_slot_state,
)
from torchdistx_tpu.models import Llama
from torchdistx_tpu.obs.recompile import RecompileWatcher
from torchdistx_tpu.serve import ServeEngine

MAX_LEN = 64
N_NEW = 7

ENGINES = {
    "slab": {},
    "paged": {"page_size": 8},
    "persistent": {"decode_mode": "persistent"},
    "speculative": {"speculate": 2},
    "persistent-speculative-paged": {
        "decode_mode": "persistent", "speculate": 2, "page_size": 8,
    },
}
PROGRAM_GETTERS = (
    "_decode_program", "_persistent_program", "_spec_decode_program",
    "_spec_persistent_program", "_prefill_program",
    "_prefill_warm_program", "_paged_prefill_program",
)


def _llama():
    tdx.manual_seed(0)
    return Llama.from_name("tiny", n_kv_heads=2, max_seq_len=MAX_LEN)


def _requests(seed=21, lengths=(6, 11, 9, 4, 13, 8)):
    """Greedy and sampled rows side by side, distinct seeds."""
    rs = np.random.RandomState(seed)
    return [
        {
            "prompt": rs.randint(0, 256, (n,)).astype(np.int32),
            "max_new_tokens": N_NEW,
            "temperature": 0.0 if i % 3 == 0 else 0.7 + 0.1 * i,
            "seed": 100 + i,
        }
        for i, n in enumerate(lengths)
    ]


def _engine(model, kind, **kw):
    opts = dict(
        num_slots=3, max_len=MAX_LEN, prefill_buckets=(16,),
        cost_cards=False,  # a card's lowering traces the model: not a step
    )
    opts.update(ENGINES[kind])
    opts.update(kw)
    return ServeEngine(model, **opts)


def _serve_staggered(engine, requests):
    """Two requests first, the rest submitted while those decode: every
    later admission is mid-run, into a freed (dirty) slot or beside
    running ones."""
    handles = [engine.submit(**r) for r in requests[:2]]
    engine.step()
    engine.step()
    handles += [engine.submit(**r) for r in requests[2:]]
    while engine.step():
        pass
    return [h.result().tokens for h in handles]


def _reference(model, request):
    """One request alone, one token a call: ``forward_cached`` and the
    slot sampler, nothing of the engine or of the decode programs."""
    params = dict(model.named_parameters())
    sampler = _make_slot_sampler(jnp.int32)
    temp = jnp.asarray([request["temperature"]], jnp.float32)
    seed = jnp.asarray([request["seed"]], jnp.int32)
    prompt = request["prompt"]

    def call(tokens, cache, pos):
        return tdx.nn.module.functional_call(
            model, params, (tokens, cache, pos), method="forward_cached"
        )

    cache = model.init_cache(1, MAX_LEN)
    logits, cache = call(jnp.asarray(prompt[None]), cache, 0)
    out = []
    for i in range(request["max_new_tokens"]):
        tok = sampler(logits[:, -1], temp, seed, jnp.asarray([i], jnp.int32))
        out.append(int(tok[0]))
        logits, cache = call(tok[:, None], cache, prompt.size + i)
    return np.asarray(out, np.int32)


def _around_dispatches(engine, monkeypatch, around):
    """Every program the engine fetches comes back wrapped:
    ``around(getter, program, args)`` makes the call and returns its
    result."""
    for getter in PROGRAM_GETTERS:
        real_getter = getattr(engine, getter)

        def get(*a, _getter=getter, _real=real_getter, **k):
            program = _real(*a, **k)
            return lambda *args: around(_getter, program, args)

        monkeypatch.setattr(engine, getter, get)


class _Spy:
    """Counts ``jnp.asarray`` / ``jax.device_put`` calls made while a
    host phase that builds a dispatch's arguments is open, and keeps the
    types of the small arguments each program was handed."""

    def __init__(self, engine, monkeypatch):
        self.calls = {"decode_args": 0, "schedule": 0}
        self.open = []  # the phases open now, innermost last
        self.small_args = []  # (program getter, [type of each small leaf])
        self.resident = []  # (program getter, device-resident arguments)
        self.pending_at_dispatch = []
        real_phase = engine._phase

        @contextlib.contextmanager
        def phase(name, *sink, **stats):
            with real_phase(name, *sink, **stats):
                self.open.append(name)
                try:
                    yield
                finally:
                    self.open.pop()

        monkeypatch.setattr(engine, "_phase", phase)
        for target, attr in ((jnp, "asarray"), (jax, "device_put")):
            monkeypatch.setattr(
                target, attr, self._counting(getattr(target, attr))
            )
        _around_dispatches(engine, monkeypatch, self._watch(engine))

    def _counting(self, real):
        def counted(*a, **k):
            # ``serve/prefill`` and ``serve/decode`` are the dispatches:
            # what the program call itself does is not the host's path
            if self.open and self.open[-1] in self.calls:
                self.calls[self.open[-1]] += 1
            return real(*a, **k)

        return counted

    def _watch(self, engine):
        def around(getter, program, args):
            # what stays on the device between programs is no transfer:
            # told apart by identity, not by type
            resident = [
                x for x in args[2:]
                if x is engine._carry or x is engine._firsts
            ]
            self.resident.append((getter, len(resident)))
            self.small_args.append((getter, [
                type(x) for x in args[2:]
                if not any(x is r for r in resident)
            ]))
            if "persistent" in getter:
                self.pending_at_dispatch.append(len(engine._pending_first))
            return program(*args)

        return around


@pytest.mark.parametrize("kind", list(ENGINES))
def test_no_host_conversion_before_a_dispatch_and_streams_exact(
    kind, monkeypatch
):
    model = _llama()
    requests = _requests()
    engine = _engine(model, kind)
    spy = _Spy(engine, monkeypatch)
    served = _serve_staggered(engine, requests)
    monkeypatch.undo()

    snap = engine.metrics.snapshot()
    assert snap["prefill_calls"] == len(requests)
    decodes = [t for g, t in spy.small_args if "prefill" not in g]
    prefills = [t for g, t in spy.small_args if "prefill" in g]
    assert len(decodes) == snap["decode_dispatches"] > 1
    assert len(prefills) == len(requests)
    # a prefill's arguments: host arrays and NumPy scalars, none converted
    assert spy.calls["schedule"] == 0
    for types in prefills:
        assert all(issubclass(t, (np.ndarray, np.generic)) for t in types)
    if engine._persistent:
        # the splice, and only the splice: the packed state goes to the
        # device once a dispatch, and one array-typed index per deferred
        # first token
        assert sum(spy.pending_at_dispatch) == len(requests)
        assert spy.calls["decode_args"] == (
            len(decodes) + sum(spy.pending_at_dispatch)
        )
        for types in decodes:
            assert issubclass(types[0], jax.Array)
            assert all(issubclass(t, np.ndarray) for t in types[1:])
    else:
        assert spy.calls["decode_args"] == 0
        for types in decodes:
            assert all(issubclass(t, np.ndarray) for t in types)
    # one packed state + the history (speculative) + the tables (paged):
    # with the row of sources the state is still ONE host array
    assert {len(t) for t in decodes} == {
        1 + bool(engine.speculate) + bool(engine.paged)
    }
    # and beside them only what lives on the device: the first tokens'
    # vector (a prefill), with the carry (the fused one-token decode)
    carries = not (engine._persistent or engine.speculate)
    assert {n for g, n in spy.resident if "prefill" in g} == {1}
    assert {n for g, n in spy.resident if "prefill" not in g} == {
        2 if carries else 0
    }
    for request, tokens in zip(requests, served):
        np.testing.assert_array_equal(tokens, _reference(model, request))


@pytest.mark.parametrize("kind", list(ENGINES))
def test_every_program_compiles_once(kind):
    """Two engines' worth of steps with admissions in every bucket: the
    second engine, and every step after the first of each program,
    compile nothing — no argument became weakly typed, none changed
    dtype between dispatches."""
    model = _llama()
    lengths = (6, 11, 20, 4, 27, 8)  # both buckets, cold

    def serve(watch=contextlib.nullcontext()):
        engine = _engine(model, kind, prefill_buckets=(16, 32))
        with watch:
            _serve_staggered(engine, _requests(5, lengths))
        return engine

    first = serve()
    programs = first.num_compiled_programs()
    if programs is None:
        pytest.skip("jit cache introspection unavailable on this jax")
    # two prefill buckets (cold; paged engines have no warm hit here)
    # and one decode program
    assert programs == 3
    watcher = RecompileWatcher(install=False)
    second = serve(watcher)
    assert second.num_compiled_programs() == programs
    assert watcher.available and watcher.total == 0, watcher.counts


@pytest.mark.parametrize("kind", list(ENGINES))
def test_dispatch_aliases_none_of_the_live_mirrors(kind, monkeypatch):
    """Garbage written over the engine's host mirrors right after a
    dispatch returns and before its sync, then restored: the tokens are
    unchanged, so the program read arrays of its own."""
    model = _llama()
    requests = _requests()
    expected = _serve_staggered(_engine(model, kind), requests)

    engine = _engine(model, kind)
    mirrors = ("_last_tok", "_temps", "_seeds", "_ntok", "_budget", "_hist")
    overwritten = []

    def clobber(getter, program, args):
        out = program(*args)
        saved = {}
        for name in mirrors:
            arr = getattr(engine, name)
            saved[name] = arr.copy()
            arr[...] = 77 if arr.dtype != np.float32 else -3.5
        tables = getattr(engine.cache, "page_tables", None)
        if tables is not None:
            saved["tables"] = tables.copy()
            tables[...] = 0
        # the outputs exist before anything is put back: the program has
        # read whatever it was going to read
        jax.block_until_ready(out)
        for name in mirrors:
            getattr(engine, name)[...] = saved[name]
        if tables is not None:
            tables[...] = saved["tables"]
        overwritten.append(getter)
        return out

    _around_dispatches(engine, monkeypatch, clobber)
    served = _serve_staggered(engine, requests)
    assert len(overwritten) > len(requests)
    for a, b in zip(expected, served):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("slots", [1, 16, 32])
def test_pack_unpack_round_trip_bit_for_bit(slots):
    rs = np.random.RandomState(slots)
    info = np.iinfo(np.int32)

    def ints():
        return rs.randint(info.min, info.max, slots, dtype=np.int64).astype(
            np.int32
        )

    temps = rs.standard_normal(slots).astype(np.float32)  # negative too
    special = np.asarray(
        [-0.0, 1e-45, -1e-40, np.inf, 3.4e38, 0.0], np.float32
    )  # signed zero, subnormals, the largest
    temps[: min(slots, special.size)] = special[:slots]
    mask = rs.randint(0, 2, slots).astype(bool)
    fields = [ints(), ints(), temps, ints(), ints(), ints(), mask]
    before = [f.copy() for f in fields]

    state = pack_slot_state(*fields)
    assert state.shape == (SLOT_STATE_ROWS, slots)
    assert state.dtype == np.int32
    assert not any(np.shares_memory(state, f) for f in fields)
    unpacked = jax.jit(_unpack_slot_state)(state)
    for got, want in zip(unpacked, before):
        got = np.asarray(got)
        assert got.dtype == want.dtype and got.shape == want.shape
        # compare BITS: -0.0 == 0.0 and a flushed subnormal would pass ==
        np.testing.assert_array_equal(
            got.view(np.uint8), want.view(np.uint8)
        )
