"""Python half of the deferred-init recorder/replayer.

The native core (``torchdistx_tpu._C``) owns graph topology, replay
scheduling, and GC; this module owns what only Python can: the op closures
themselves and their execution on XLA devices.  This mirrors the reference's
split where C++ `Op` objects hold a boxed-call closure replayed through the
dispatcher (reference src/cc/torchdistx/deferred_init.cc:157-272) — here the
"dispatcher" is JAX: replay executes the schedule op-by-op on the target
device, leaning on JAX's eager primitive cache so repeated layer structures
compile once, with sharded targets placed into their shard layout the moment
they are produced (see ``RecordingSession._replay`` for the measured
rationale).
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from ._C import NODE_RECORDED, NativeGraph

# dtype <-> int code table for the native metadata store.
_DTYPE_CODES: dict[Any, int] = {}
_CODE_DTYPES: dict[int, Any] = {}
for _i, _name in enumerate(
    [
        "float32", "float64", "float16", "bfloat16",
        "int8", "int16", "int32", "int64",
        "uint8", "uint16", "uint32", "uint64",
        "bool", "complex64", "complex128",
        "float8_e4m3fn", "float8_e5m2",
    ]
):
    try:
        _dt = jnp.dtype(_name)
    except TypeError:
        continue
    _DTYPE_CODES[_dt] = _i
    _CODE_DTYPES[_i] = _dt


def dtype_code(dtype: Any) -> int:
    return _DTYPE_CODES.get(jnp.dtype(dtype), -1)


@dataclasses.dataclass(frozen=True)
class NodeRef:
    """Placeholder inside a recorded closure's args for a graph dependency."""

    node: int
    out_idx: int


# -- record-time safety ------------------------------------------------------
# The reference validates immutable argument types and version-counters
# external tensors so a mutation between record and replay cannot silently
# change materialization (reference deferred_init.cc:227-254,464-496,640-667).
# numpy args here are either deep-copied at record (small: replay is then
# bit-identical to eager init regardless of later mutation) or fingerprinted
# (large: replay re-checks the fingerprint and raises loudly on mismatch —
# the version-counter analog, without doubling host RAM for big buffers).

_COPY_THRESHOLD_BYTES = 1 << 20  # 1 MiB


def _fingerprint(x) -> tuple:
    import zlib

    import numpy as np

    if x.size == 0:
        digest = 0
    else:
        # full crc32: deterministic detection of any content change.  Large
        # recorded numpy args are rare (ctor constants are small; the HF
        # interop path does not record raw weights), so the linear scan at
        # record + replay is cheap in practice.
        digest = zlib.crc32(np.ascontiguousarray(x).data)
    return (tuple(x.shape), str(x.dtype), x.nbytes, digest)


@dataclasses.dataclass(frozen=True)
class GuardedArg:
    """A large mutable (numpy) closure argument captured by reference with a
    record-time fingerprint, re-verified at replay."""

    value: Any
    fingerprint: tuple

    def resolve(self) -> Any:
        if _fingerprint(self.value) != self.fingerprint:
            raise RuntimeError(
                "a numpy array captured at record time was mutated before "
                "materialization; deferred replay would silently diverge "
                "from eager init (the reference's version-counter check, "
                "deferred_init.cc:640-667, raises here too). Re-record, or "
                "avoid mutating arrays passed to ops inside deferred_init()."
            )
        return self.value


def guard_mutable(x: Any) -> Any:
    """Make a closure-captured leaf safe against external mutation."""
    import numpy as np

    if isinstance(x, np.ndarray):
        if x.nbytes <= _COPY_THRESHOLD_BYTES:
            return np.array(x, copy=True)
        return GuardedArg(x, _fingerprint(x))
    return x


# jax config entries reinstated at replay — the analog of the reference's
# captured ThreadLocalState (deferred_init.cc:205-215,261-266): replay under
# a different ambient precision/x64 context must still match eager init.
_CAPTURED_CONFIG = (
    "jax_default_matmul_precision",
    "jax_enable_x64",
    "jax_numpy_dtype_promotion",
)


def capture_context() -> dict[str, Any]:
    out = {}
    for k in _CAPTURED_CONFIG:
        v = getattr(jax.config, k, None)
        out[k] = v.value if hasattr(v, "value") else v
    return out


@dataclasses.dataclass
class OpClosure:
    """A recorded op: pure function + args with NodeRef placeholders +
    captured execution context."""

    fn: Callable[..., Any]
    args: tuple[Any, ...]
    kwargs: dict[str, Any]
    n_outputs: int  # flattened output count
    out_treedef: Any  # treedef to unflatten fn's output
    tls: Optional[dict[str, Any]] = None  # captured jax config context
    _fn_sig: Any = None  # memoized _callable_sig (immutable per closure)

    @property
    def fn_sig(self) -> Any:
        if self._fn_sig is None:
            self._fn_sig = _callable_sig(self.fn)
        return self._fn_sig

    def call(
        self,
        env: dict[tuple[int, int], Any],
        ambient: Optional[dict[str, Any]] = None,
    ) -> list[Any]:
        def resolve(x: Any) -> Any:
            if isinstance(x, NodeRef):
                return env[(x.node, x.out_idx)]
            if isinstance(x, GuardedArg):
                return x.resolve()
            return x

        is_placeholder = lambda x: isinstance(x, (NodeRef, GuardedArg))  # noqa: E731
        args = jax.tree_util.tree_map(
            resolve, self.args, is_leaf=is_placeholder
        )
        kwargs = jax.tree_util.tree_map(
            resolve, self.kwargs, is_leaf=is_placeholder
        )
        out = self._run(args, kwargs, ambient)
        leaves = jax.tree_util.tree_leaves(out)
        return leaves

    def _run(self, args, kwargs, ambient: Optional[dict[str, Any]] = None):
        # fast path: jax.config attribute reads are not free, and a replay
        # executes thousands of closures — when the caller has already
        # captured the ambient config once (capture_context()), an
        # equality check replaces three per-op config round-trips
        if not self.tls or (ambient is not None and ambient == self.tls):
            return self.fn(*args, **kwargs)
        saved = {}
        try:
            for k, v in self.tls.items():
                cur = getattr(jax.config, k)
                cur = cur.value if hasattr(cur, "value") else cur
                if cur != v:
                    saved[k] = cur
                    jax.config.update(k, v)
            return self.fn(*args, **kwargs)
        finally:
            for k, v in saved.items():
                jax.config.update(k, v)


class RecordingSession:
    """One deferred-init recording: native graph + closures + replay cache.

    Thread-safety follows the reference's model: mode state is thread-local
    (reference fake.cc:554,588) but a session's graph is shared, so closure
    and cache maps are guarded by a lock.

    ``replay_mode`` selects the executor:
      - "eager" (default): op-by-op on-device execution.  JAX's eager
        primitive cache gives each repeated (op, shape) one compilation;
        measured 7-10x faster end-to-end than one whole-model jit, whose
        XLA compile time scales with the giant replay graph.
      - "chunked": the schedule is cut into fixed-size chunks, each traced
        and jitted as one function, with the jit cache keyed by the
        chunk's (op names, external aval) signature — structurally
        repeated layers share one compile.  Each chunk is ONE dispatch
        instead of chunk_size of them.  XLA fusion inside a chunk
        may reassociate float math: chunked materialization matches eager
        init to ~1 ulp, not bit-for-bit (eager mode keeps bit-identity).
      - "auto": pick per graph + platform (``_choose_replay_mode``) by
        comparing estimated COMPILE counts.  A transformer's init
        schedule repeats a few (op, shape) signatures (Llama: ~6
        distinct closures), so eager's primitive cache already pays
        ~one layer's compiles and wins on TPU (on-chip A/B below).  A
        conv net's schedule is shape-diverse (ResNet-50: 34 distinct
        conv/BN closure sigs, ~160 primitive compiles), so eager pays
        one compile per distinct shape while chunking collapses it to a
        handful of repeated chunk compiles (7 on ResNet-50).  Off-TPU
        eager is taken uniformly.
    Class attributes so benchmarks can flip globally; per-instance
    override allowed.

    "eager" is the default: it is the bit-identical mode, and on the
    v5e Llama-2-7B materializes through it in 17 s when its ~14 init
    programs must compile and 0.3 s once they have (chip_smoke.py,
    PR 24; PERF.md).
    Chunked has no chip measurement on this machine; ROADMAP C5 decides
    whether it stays.
    """

    replay_mode: str = "eager"
    chunk_size: int = 48
    # "auto" weight: one chunk compile costs roughly this many primitive
    # compiles (a chunk traces ~chunk_size ops into one XLA graph).  Rough,
    # re-calibratable on hardware; the decision is insensitive except near
    # the crossover.
    chunk_compile_factor: float = 4.0

    def __init__(self) -> None:
        self.graph = NativeGraph()
        self._lock = threading.RLock()
        self.closures: dict[int, OpClosure] = {}
        # (node, out_idx) -> materialized jax.Array
        self.cache: dict[tuple[int, int], Any] = {}
        # node -> number of live FakeArray handles (mirrors native pins so the
        # replay executor knows which outputs must survive the fused jit call)
        self.pins: dict[int, int] = {}
        # chunked-replay jit cache: signature -> compiled chunk executor
        self._chunk_cache: dict[Any, Any] = {}
        # schedule-names hash -> (period, start), so repeated replays of
        # the same session don't re-run period detection
        self._period_cache: dict[Any, Any] = {}
        # observability: compiles vs dispatches (survive cache clearing)
        self.chunk_compiles = 0
        self.chunk_dispatches = 0
        # numerics observatory (obs.numerics, TDX_NUMERICS): each chunk
        # dispatch carries ONE fused digest of its inexact outputs as an
        # extra program output; digests park here and fold into the book
        # lazily at the end of the chunked replay (the arrays are this
        # replay's own outputs — fetching them adds no dispatch).  The
        # book is created on first harvest so a numerics-off session
        # pays nothing, not even the import.
        self.numerics_book: Any = None
        self._pending_chunk_digests: list = []
        # unhashable static-leaf tokens for _eager_compile_sig: id -> a
        # (monotonic token, held ref) pair (see leaf_sig)
        self._static_sig_tokens: dict[int, tuple] = {}
        self._static_sig_counter = itertools.count()

    # -- recording ---------------------------------------------------------

    def record(
        self,
        name: str,
        fn: Callable[..., Any],
        args: tuple[Any, ...],
        kwargs: dict[str, Any],
        out_avals: Sequence[jax.ShapeDtypeStruct],
        out_treedef: Any,
        deps: Sequence[int],
        tls: Optional[dict[str, Any]] = None,
    ) -> int:
        with self._lock:
            nid = self.graph.record_op(name, list(deps), len(out_avals))
            for i, aval in enumerate(out_avals):
                if hasattr(aval, "shape") and hasattr(aval, "dtype"):
                    self.graph.set_output_meta(
                        nid, i, tuple(aval.shape), dtype_code(aval.dtype)
                    )
            self.closures[nid] = OpClosure(
                fn=fn,
                args=args,
                kwargs=kwargs,
                n_outputs=len(out_avals),
                out_treedef=out_treedef,
                tls=tls,
            )
            return nid

    def pin(self, node: int) -> None:
        with self._lock:
            self.graph.pin(node)
            self.pins[node] = self.pins.get(node, 0) + 1

    def unpin(self, node: int) -> None:
        with self._lock:
            release = self.graph.unpin(node)
            n = self.pins.get(node, 0) - 1
            if n <= 0:
                self.pins.pop(node, None)
            else:
                self.pins[node] = n
            if release:
                self.closures.pop(node, None)
                for k in [k for k in self.cache if k[0] == node]:
                    del self.cache[k]

    # -- replay ------------------------------------------------------------

    def materialize_many(
        self,
        targets: Sequence[tuple[int, int]],
        shardings: Sequence[Optional[jax.sharding.Sharding]],
        devices: Sequence[Optional[Any]],
    ) -> list[Any]:
        """Materialize many outputs in one eager replay pass.

        This is the hot path for ``materialize_module``: the union of all
        targets' schedules is executed once, in chronological order, with
        each target placed into its (possibly sharded) buffers as soon as it
        is produced.
        """
        with self._lock:
            resolved_shardings: list[Optional[jax.sharding.Sharding]] = []
            for sh, dev in zip(shardings, devices):
                if sh is None and dev is not None:
                    sh = jax.sharding.SingleDeviceSharding(dev)
                resolved_shardings.append(sh)

            # Union schedule over all not-yet-cached targets.
            pending = [
                t
                for t in targets
                if t not in self.cache
                and self.graph.node_state(t[0]) == NODE_RECORDED
            ]
            sched_set: set[int] = set()
            for node, _ in pending:
                sched_set.update(self.graph.collect_schedule(node))
            sched = sorted(sched_set)

            if sched:
                # Replay must execute for REAL: suspend the caller's
                # fake/deferred mode so recorded creation closures that call
                # the interposed jnp surface (ops._intercept) do not re-fake
                # and record stray nodes mid-replay.  This bites when a
                # terminal op forces materialization *inside* an active
                # deferred_init() (the reference handles it with its
                # NoDeferredInit RAII guard around replay,
                # deferred_init.cc:769).
                from .fake import no_deferred_init

                with no_deferred_init():
                    self._replay(
                        sched,
                        sched_set,
                        set(pending),
                        resolved_targets={
                            t: s
                            for t, s in zip(targets, resolved_shardings)
                        },
                    )

            out: list[Any] = []
            for t, sh in zip(targets, resolved_shardings):
                val = self.cache.get(t)
                if val is None:
                    raise RuntimeError(
                        f"replay did not produce output {t[1]} of node {t[0]}"
                    )
                if sh is not None and not val.sharding.is_equivalent_to(
                    sh, val.ndim
                ):
                    # re-materialization under a different placement returns
                    # a resharded copy; the canonical cached object (identity
                    # preservation) is untouched
                    val = jax.device_put(val, sh)
                out.append(val)
            return out

    def _replay(
        self,
        sched: list[int],
        sched_set: set[int],
        target_keys: set[tuple[int, int]],
        resolved_targets: dict[tuple[int, int], Optional[jax.sharding.Sharding]],
    ) -> None:
        """Execute the schedule eagerly on-device; cache kept outputs; GC.

        Eager (op-by-op) replay is the deliberate performance choice here:
        init subgraphs repeat structurally across a model's layers, and
        JAX's eager primitive cache gives each repeated (op, shape) a single
        compilation — materializing a 36-layer model costs ~the compiles of
        one layer.  A whole-model fused jit was measured 7-10x slower
        end-to-end because XLA compile time scales with the giant replay
        graph (GPT-2-large: 35 s fused vs eager ~4 s on one TPU chip), and
        fusion buys nothing for init ops that execute once.

        Memory discipline for multi-billion-parameter replays:
          - targets with a requested sharding are ``device_put`` into their
            shard layout immediately, so the full single-device array is
            transient (one parameter at a time);
          - every intermediate's buffer is dropped as soon as its last
            in-schedule consumer has executed (refcounts below), so peak
            device memory stays ~(final params) + (one layer's temps).
        """
        # Outputs that must survive this replay beyond the loop.
        keep: set[tuple[int, int]] = set()
        for nid in sched:
            closure = self.closures[nid]
            must_keep = self.pins.get(nid, 0) > 0 or any(
                (nid, i) in target_keys for i in range(closure.n_outputs)
            )
            if not must_keep:
                must_keep = any(
                    d not in sched_set
                    and self.graph.node_state(d) == NODE_RECORDED
                    for d in self.graph.dependents(nid)
                )
            if must_keep:
                keep.update((nid, i) for i in range(closure.n_outputs))

        # In-schedule consumer refcounts for prompt buffer release.
        uses: dict[int, int] = {nid: 0 for nid in sched}
        ext_inputs: dict[tuple[int, int], Any] = {}
        for nid in sched:
            for arg in _iter_noderefs(self.closures[nid]):
                if arg.node in uses:
                    uses[arg.node] += 1
                else:
                    ext_inputs[(arg.node, arg.out_idx)] = self.cache[
                        (arg.node, arg.out_idx)
                    ]

        env: dict[tuple[int, int], Any] = dict(ext_inputs)
        ambient = capture_context()

        def emit(nid, outs):
            for i, o in enumerate(outs):
                key = (nid, i)
                sharding = resolved_targets.get(key)
                if sharding is not None:
                    o = jax.device_put(o, sharding)
                env[key] = o
                if key in keep:
                    self.cache[key] = o
            # release producers whose last in-schedule consumer just ran
            for arg in _iter_noderefs(self.closures[nid]):
                if arg.node in uses:
                    uses[arg.node] -= 1
                    if uses[arg.node] == 0 and not any(
                        (arg.node, j) in keep
                        for j in range(self.closures[arg.node].n_outputs)
                    ):
                        for j in range(self.closures[arg.node].n_outputs):
                            env.pop((arg.node, j), None)

        mode = self.replay_mode
        if mode not in ("eager", "chunked", "auto"):
            raise ValueError(
                f"unknown replay_mode {mode!r} "
                "(expected 'eager', 'chunked' or 'auto')"
            )
        if mode == "auto":
            mode = self._choose_replay_mode(sched)
        from .obs.trace import get_tracer

        with get_tracer().span(
            f"replay/{mode}", cat="replay", ops=len(sched)
        ):
            if mode == "chunked":
                self._replay_chunked(sched, env, emit, ambient)
            else:
                for nid in sched:
                    outs = self.closures[nid].call(env, ambient)
                    emit(nid, outs)

        for nid in sched:
            released = self.graph.mark_materialized(nid)
            for rid in released:
                self.closures.pop(rid, None)
                for k in [k for k in self.cache if k[0] == rid]:
                    del self.cache[k]

        # a fully materialized graph will never replay again: drop the
        # chunk executors (their traces pin the closure fns they captured)
        if self.graph.num_materialized() == self.graph.num_nodes():
            self._chunk_cache.clear()
            self._period_cache.clear()

    # -- auto replay-mode selection ---------------------------------------

    def _eager_compile_sig(self, nid: int):
        """Proxy for the eager primitive-cache key of one closure: the op
        fn + every static leaf (shape tuples, dtypes, scalars) + the
        shape/dtype of every array-valued leaf.  Two closures with equal
        signatures hit one eager compile between them."""
        c = self.closures[nid]

        def leaf_sig(x):
            if isinstance(x, NodeRef):
                # both real caches key on input avals (JAX's primitive
                # cache, and the chunk cache's ext-aval tuple) — a bare
                # ("ref",) would collapse shape-distinct inputs and
                # mispredict both estimates
                try:
                    shape, code = self.graph.get_output_meta(
                        x.node, x.out_idx
                    )
                    return ("ref", tuple(shape), code)
                except Exception:
                    return ("ref",)
            if isinstance(x, GuardedArg):
                x = x.value
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                return ("arr", tuple(x.shape), str(x.dtype))
            try:
                return ("static", _freeze(x))
            except TypeError:
                # unhashable static leaf: assign a session-lifetime token
                # (id() alone could be reused after GC within a session
                # and collapse two distinct closures' signatures); the
                # held reference is bounded by the recorded graph's size
                key = id(x)
                ent = self._static_sig_tokens.get(key)
                if ent is None or ent[1] is not x:
                    ent = (next(self._static_sig_counter), x)
                    self._static_sig_tokens[key] = ent
                return ("static-id", ent[0])

        is_ph = lambda x: isinstance(x, (NodeRef, GuardedArg))  # noqa: E731
        leaves, _ = jax.tree_util.tree_flatten(
            (c.args, c.kwargs), is_leaf=is_ph
        )
        return (c.fn_sig, tuple(leaf_sig(x) for x in leaves))

    def _choose_replay_mode(
        self, sched: list[int], platform: Optional[str] = None
    ) -> str:
        """The "auto" policy (class docstring): estimate each executor's
        COMPILE count from the schedule alone and pick the cheaper.

        Eager pays ~one primitive-cache compile per distinct closure
        signature; chunked pays ~one (heavier, ``chunk_compile_factor``-
        weighted) compile per distinct chunk signature.  A conv net's
        many distinct conv/BN shapes collapse into a few repeated chunks
        (ResNet-50: 34 closure sigs vs 7 chunks), while a transformer's
        few closure sigs are already cheaper than any chunking (Llama:
        ~6).  Off-accelerator there is no device-roundtrip per compile
        and eager's primitive cache is uniformly cheapest."""
        if platform is None:
            platform = jax.devices()[0].platform
        if platform not in ("tpu", "gpu"):
            return "eager"
        if not sched:
            return "eager"
        sigs = {n: self._eager_compile_sig(n) for n in sched}
        eager_compiles = len(set(sigs.values()))
        bounds = self._schedule_bounds(sched)
        chunk_sigs = {tuple(sigs[n] for n in sched[a:b]) for a, b in bounds}
        chunked_cost = len(chunk_sigs) * self.chunk_compile_factor
        return "chunked" if chunked_cost < eager_compiles else "eager"

    def _schedule_bounds(self, sched: list[int]) -> list[tuple[int, int]]:
        """Period-aligned chunk boundaries for a schedule (shared by the
        chunked executor and the auto estimator; period detection cached
        per schedule-names hash)."""
        names = [self.graph.name(n) for n in sched]
        key = hash(tuple(names))
        if key not in self._period_cache:
            self._period_cache[key] = _detect_period(names)
        return _chunk_bounds(
            names, self.chunk_size, period_hint=self._period_cache[key]
        )

    # -- chunked replay ----------------------------------------------------

    def _replay_chunked(self, sched, env, emit, ambient) -> None:
        """Execute the schedule in jitted chunks aligned to the model's
        repeating layer structure.

        Each chunk is one compiled executable — one dispatch instead of
        ``chunk_size`` eager ones.  The jit cache is keyed by the chunk's structural
        signature (op code objects + recursively-hashed static closure
        cells + argument wiring + external/dynamic avals), so repeated
        chunks share one compilation.  Sharing only pays off when chunk
        boundaries land at the same offset of every repeated layer, so the
        op-name sequence's period is detected and boundaries are cut at
        ``prologue + k*period (+ j*chunk_size within a long period)``;
        without a detectable period, fixed-size chunks are used (correct,
        just compile-heavier).
        """
        for a, b in self._schedule_bounds(sched):
            self._run_chunk(sched[a:b], env, emit, ambient)
        self._harvest_chunk_digests()

    def _harvest_chunk_digests(self) -> None:
        """Fold every parked per-chunk digest into the session's
        :class:`~torchdistx_tpu.obs.numerics.NumericsBook` under the
        ``replay/chunk`` site.  Called once per chunked replay, AFTER
        all chunks dispatched — the digests are outputs of dispatches
        the replay already made, so this is a fetch, never a new one."""
        if not self._pending_chunk_digests:
            return
        pend, self._pending_chunk_digests = self._pending_chunk_digests, []
        from .obs.numerics import NumericsBook

        if self.numerics_book is None:
            self.numerics_book = NumericsBook()
        for d in jax.device_get(pend):
            self.numerics_book.update_tree({"replay/chunk": d})

    def _run_chunk(self, chunk, env, emit, ambient) -> None:
        closures = [self.closures[n] for n in chunk]

        # per-op captured config must be uniform and equal to the ambient
        # for a single jitted chunk; anything else falls back to eager
        tls_list = [dict(c.tls) if c.tls else None for c in closures]
        if any(t != tls_list[0] for t in tls_list) or (
            tls_list[0] is not None and tls_list[0] != ambient
        ):
            for nid in chunk:
                emit(nid, self.closures[nid].call(env, ambient))
            return

        in_chunk = {n: j for j, n in enumerate(chunk)}

        # discover external NodeRef inputs (ordered, deduped) and dynamic
        # (array / guarded) leaves per closure, replacing each with a
        # _Slot placeholder so the plan is value-free
        ext_keys: list[tuple[int, int]] = []
        ext_index: dict[tuple[int, int], int] = {}
        dyn_vals: list[Any] = []
        plans = []  # per closure: (args, kwargs) with _Slot leaves
        sig_parts = []

        def plan_leaf(x, sig_acc):
            if isinstance(x, NodeRef):
                if x.node in in_chunk:
                    sig_acc.append(("loc", in_chunk[x.node], x.out_idx))
                    return _Slot("loc", in_chunk[x.node], x.out_idx)
                key = (x.node, x.out_idx)
                if key not in ext_index:
                    ext_index[key] = len(ext_keys)
                    ext_keys.append(key)
                sig_acc.append(("ext", ext_index[key]))
                return _Slot("ext", ext_index[key])
            if isinstance(x, GuardedArg):
                v = x.resolve()  # fingerprint re-verified per run
                dyn_vals.append(v)
                sig_acc.append(("dyn", tuple(v.shape), str(v.dtype)))
                return _Slot("dyn", len(dyn_vals) - 1)
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                dyn_vals.append(x)
                sig_acc.append(("dyn", tuple(x.shape), str(x.dtype)))
                return _Slot("dyn", len(dyn_vals) - 1)
            try:
                sig_acc.append(("static", _freeze(x)))
            except TypeError:
                sig_acc.append(("static-id", id(x)))  # unshareable
            return _Slot("static", x)

        is_ph = lambda x: isinstance(x, (NodeRef, GuardedArg))  # noqa: E731
        for c in closures:
            acc: list = [c.fn_sig, c.n_outputs]
            planned_args = jax.tree_util.tree_map(
                lambda x: plan_leaf(x, acc), c.args, is_leaf=is_ph
            )
            planned_kwargs = jax.tree_util.tree_map(
                lambda x: plan_leaf(x, acc), c.kwargs, is_leaf=is_ph
            )
            plans.append((planned_args, planned_kwargs))
            sig_parts.append(tuple(_freeze(s) for s in acc))

        ext_vals = [env[k] for k in ext_keys]
        # numerics flag joins the signature: a digest-carrying chunk
        # program has one extra output and must never share an
        # executable with the plain one (toggling TDX_NUMERICS between
        # replays retraces rather than mis-unpacks)
        from .obs.numerics import numerics_enabled

        num_on = numerics_enabled()
        sig = (
            tuple(sig_parts),
            tuple((tuple(v.shape), str(v.dtype)) for v in ext_vals),
            tuple(sorted(tls_list[0].items())) if tls_list[0] else None,
            num_on,
        )

        self.chunk_dispatches += 1
        entry = self._chunk_cache.get(sig)
        if entry is None:
            self.chunk_compiles += 1
            # capture only what the trace needs — fns and value-free plans
            # (GuardedArg values already moved to dyn inputs) — NOT the
            # OpClosure objects, whose args would pin host buffers in the
            # cache after graph GC frees the closures themselves
            fns = [c.fn for c in closures]

            def chunk_fn(ext_in, dyn_in):
                local: list[list[Any]] = []

                def fill(ph: "_Slot"):
                    if ph.kind == "loc":
                        return local[ph.a][ph.b]
                    if ph.kind == "ext":
                        return ext_in[ph.a]
                    if ph.kind == "dyn":
                        return dyn_in[ph.a]
                    return ph.a  # static

                is_p = lambda x: isinstance(x, _Slot)  # noqa: E731
                for fn, (pa, pk) in zip(fns, plans):
                    args = jax.tree_util.tree_map(fill, pa, is_leaf=is_p)
                    kwargs = jax.tree_util.tree_map(fill, pk, is_leaf=is_p)
                    out = fn(*args, **kwargs)
                    local.append(jax.tree_util.tree_leaves(out))
                flat: list[Any] = []
                for outs in local:
                    flat.extend(outs)
                if num_on:
                    # one fused digest over the chunk's inexact outputs
                    # — traced into the SAME executable, one extra
                    # output, zero extra dispatches
                    from .obs.numerics import (
                        array_digest,
                        merge_digests,
                        zero_digest,
                    )

                    d = zero_digest()
                    for x in flat:
                        if hasattr(x, "dtype") and jnp.issubdtype(
                            x.dtype, jnp.inexact
                        ):
                            d = merge_digests(d, array_digest(x))
                    return flat, d
                return flat

            entry = jax.jit(chunk_fn)
            self._chunk_cache[sig] = entry
            # cost observatory (obs.cost): card each distinct chunk
            # program — OPT-IN via TDX_COST_CARDS because a card costs
            # one extra XLA compile and chunked replay's whole value is
            # its compile/dispatch economics (an always-on probe would
            # double exactly what bench.py measures)
            from .obs.cost import cards_enabled

            if cards_enabled():
                try:
                    from .obs.cost import compute_cost_card, default_book

                    compute_cost_card(
                        entry,
                        ext_vals,
                        dyn_vals,
                        name=f"replay/chunk/{self.chunk_compiles}",
                        book=default_book(),
                    )
                except Exception:
                    pass  # a cost probe must never fail a replay

        # one span + recompile-attribution scope per chunk dispatch: a
        # replay whose chunk cache stops hitting shows up as compiles
        # under "replay/chunk" in any installed RecompileWatcher, and
        # the Perfetto trace shows one span per dispatch
        from .obs.recompile import recompile_scope
        from .obs.trace import get_tracer

        with get_tracer().span(
            "replay/chunk", cat="replay", ops=len(chunk)
        ), recompile_scope("replay/chunk"):
            flat = entry(ext_vals, dyn_vals)
        if num_on:
            flat, dig = flat
            self._pending_chunk_digests.append(dig)
        pos = 0
        for nid, c in zip(chunk, closures):
            emit(nid, flat[pos : pos + c.n_outputs])
            pos += c.n_outputs

    def can_materialize(self, node: int) -> bool:
        with self._lock:
            return (
                self.graph.node_state(node) != NODE_RECORDED
                or node in self.closures
            )

    def materialize(
        self,
        node: int,
        out_idx: int,
        sharding: Optional[jax.sharding.Sharding] = None,
        device: Optional[Any] = None,
    ) -> Any:
        """Replay the minimal schedule producing ``node`` and return its
        output, placed on ``device`` / into ``sharding`` — no host
        round-trip; previously-materialized dependencies are consumed from
        the replay cache rather than recomputed."""
        return self.materialize_many([(node, out_idx)], [sharding], [device])[0]


def _detect_period(names: list, max_period: int = 512):
    """Smallest shift p such that ~90% of the sequence self-matches under
    it — the op-count of one repeated layer.  Also returns the start of
    the periodic region (end of the init prologue)."""
    n = len(names)
    for p in range(2, min(max_period, n // 2) + 1):
        allowed_miss = int(0.1 * (n - p))
        misses = 0
        for i in range(n - p):
            if names[i] != names[i + p]:
                misses += 1
                if misses > allowed_miss:
                    break
        if misses <= allowed_miss:
            # locate where periodicity begins (skip embedding/prologue ops)
            start = 0
            for i in range(n - p):
                if names[i] != names[i + p]:
                    start = i + 1
                else:
                    # require a full period of matches from here
                    if all(
                        names[j] == names[j + p]
                        for j in range(i, min(i + p, n - p))
                    ):
                        break
            return p, start
    return None, 0


def _chunk_bounds(names: list, chunk_size: int, period_hint=None) -> list:
    """Chunk boundaries over ``names``: period-aligned when a repeating
    layer structure is detected, else fixed-size.  Periods shorter than
    ``chunk_size`` are grouped (still signature-aligned) so the dispatch
    batching survives fine-grained op patterns."""
    n = len(names)
    p, start = period_hint if period_hint is not None else _detect_period(names)
    bounds = []

    def fixed(a, end):
        while a < end:
            bounds.append((a, min(a + chunk_size, end)))
            a = min(a + chunk_size, end)
        return a

    if p is None:
        fixed(0, n)
        return bounds
    a = fixed(0, start)  # prologue (ends exactly at `start`)
    group = max(1, chunk_size // p)  # whole periods per chunk when p small

    def period_matches(at):
        return at + p <= n and all(
            names[at + j] == names[start + j] for j in range(p)
        )

    while period_matches(a):
        if p >= chunk_size:
            # cut each period at the same internal offsets, so a chunk at
            # offset j of layer k shares its signature with layer k+1's
            for off in range(0, p, chunk_size):
                bounds.append((a + off, a + min(off + chunk_size, p)))
            a += p
        else:
            run_start = a
            k = 0
            while k < group and period_matches(a):
                a += p
                k += 1
            bounds.append((run_start, a))
    # epilogue
    fixed(a, n)
    return bounds


@dataclasses.dataclass(frozen=True)
class _Slot:
    """Value-free placeholder in a chunk plan: a chunk-local output
    ("loc", closure_idx, out_idx), an external env input ("ext", idx), a
    dynamic array input ("dyn", idx), or an inline static ("static",
    value)."""

    kind: str
    a: Any = None
    b: Any = None


def _value_sig(v: Any, depth: int):
    """Signature of one captured value (closure cell or default arg)."""
    if callable(v) and not isinstance(v, type):
        return _callable_sig(v, depth + 1)
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        return ("arr-id", id(v))  # value-bearing: unshareable
    try:
        hash(v)
        return ("val", v)
    except TypeError:
        try:
            return ("val-frozen", _freeze(v))
        except Exception:
            return ("val-id", id(v))


def _callable_sig(fn: Any, depth: int = 0):
    """Best-effort structural identity of a (possibly nested) closure:
    code object + recursively hashed static cell contents + default
    arguments + bound receiver.  Arrays, unhashables, and bound ``self``
    objects yield an id()-based token, making the signature unique (no
    sharing) rather than wrong."""
    if depth > 4:
        return ("deep", id(fn))
    # bound methods: receiver state can differ per layer — unshareable by
    # identity, with the underlying function still structurally keyed
    self_obj = getattr(fn, "__self__", None)
    if self_obj is not None:
        return (
            "bound",
            id(self_obj),
            _callable_sig(fn.__func__, depth + 1),
        )
    code = getattr(fn, "__code__", None)
    if code is None:
        # builtins / jnp functions: identity is the function object
        return ("obj", id(fn))
    sig = []
    for cell in getattr(fn, "__closure__", None) or ():
        try:
            v = cell.cell_contents
        except ValueError:  # empty cell
            sig.append(("empty",))
            continue
        sig.append(_value_sig(v, depth))
    # late-binding idiom `lambda x, scale=s: ...` stores s in __defaults__,
    # not in a cell — it must key the signature too
    defaults = tuple(
        _value_sig(v, depth) for v in getattr(fn, "__defaults__", None) or ()
    )
    kwdefaults = tuple(
        (k, _value_sig(v, depth))
        for k, v in sorted((getattr(fn, "__kwdefaults__", None) or {}).items())
    )
    return ("code", code, tuple(sig), defaults, kwdefaults)


def _freeze(x: Any):
    """Hashable view of nested lists/tuples/dicts of hashables."""
    if isinstance(x, (list, tuple)):
        return tuple(_freeze(v) for v in x)
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    hash(x)
    return x


def _iter_noderefs(closure: OpClosure):
    for leaf in jax.tree_util.tree_leaves(
        (closure.args, closure.kwargs),
        is_leaf=lambda x: isinstance(x, NodeRef),
    ):
        if isinstance(leaf, NodeRef):
            yield leaf
