"""tdx-lint rule pack: the repo's invariants as AST checks.

Each rule cites the convention it encodes (see docs/static_analysis.md
for the full catalog with provenance).  Rules are deliberately lexical —
they run on stdlib ``ast`` with no imports of jax — so the linter works
in a bare CI container and never touches a device.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding, LintContext, Rule

# ---------------------------------------------------------------------------
# shared AST helpers


def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.random.PRNGKey' for an Attribute/Name chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _last(name: Optional[str]) -> str:
    return name.rsplit(".", 1)[-1] if name else ""


_JIT_NAMES = {"jax.jit", "jit", "jax.pmap", "pmap"}
_PARTIAL_NAMES = {"functools.partial", "partial"}


def _is_jit_call(call: ast.Call) -> bool:
    """True for jit(...)/jax.jit(...) and partial(jax.jit, ...)."""
    name = _dotted(call.func)
    if name in _JIT_NAMES:
        return True
    if name in _PARTIAL_NAMES and call.args:
        return _dotted(call.args[0]) in _JIT_NAMES
    return False


def _has_kwarg(call: ast.Call, *names: str) -> bool:
    return any(kw.arg in names for kw in call.keywords)


def _has_splat(call: ast.Call) -> bool:
    return any(kw.arg is None for kw in call.keywords)


# ---------------------------------------------------------------------------


#: call names whose results count as plan-derived carry shardings — the
#: ShardingPlan projections plus the engine/fsdp helpers they subsume
_PLAN_SOURCES = {
    "shardings_for",
    "donated_carry_shardings",
    "optimizer_state_shardings",
    "param_shardings",
    "carry_shardings",
    "_out_shardings",
}


class DonatedJitNeedsOutShardings(Rule):
    """TDX101 — every donated carry cites a plan.

    Convention: jit does NOT propagate input shardings into outputs it
    considers fresh (zeros_like optimizer state, donated carries), so a
    ``donate_argnums=`` jit silently decays to replicated outputs unless
    ``out_shardings`` pins them (the optimizer-state/serve-carry lesson;
    see parallel/plan.py).  A ``**kwargs`` splat counts as satisfied —
    the caller owns the decision there.

    v2 (plan engine): the *value* passed as ``out_shardings`` must be
    plan-derived — ``plan.shardings_for(...)`` or one of the projections
    it subsumes (``donated_carry_shardings``, ``optimizer_state_
    shardings``, ``param_shardings``, ``carry_shardings``,
    ``_out_shardings``), directly or via a local variable assigned from
    such a call (tuple-unpack included).  A hand-built
    ``NamedSharding(...)`` — bare, or inside a dict/list/tuple literal —
    at a donation site is flagged: hand-rolled layouts drift from the
    plan the audit and the ledger counters price, breaking
    plan == audit == counters.
    """

    rule_id = "TDX101"
    severity = "error"
    summary = "donated jit lacks plan-derived out_shardings"

    @staticmethod
    def _var_exprs(tree: ast.AST) -> Dict[str, ast.AST]:
        """name -> assigned value expr, for simple and tuple-unpack
        assignments (each unpacked name inherits the RHS call)."""
        out: Dict[str, ast.AST] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            else:
                continue
            for tgt in targets:
                if isinstance(tgt, ast.Name):
                    out[tgt.id] = value
                elif isinstance(tgt, (ast.Tuple, ast.List)):
                    for i, el in enumerate(tgt.elts):
                        if not isinstance(el, ast.Name):
                            continue
                        if isinstance(
                            value, (ast.Tuple, ast.List)
                        ) and i < len(value.elts):
                            out[el.id] = value.elts[i]
                        else:
                            # p_sh, o_sh = plan.shardings_for(...):
                            # each name inherits the call's provenance
                            out[el.id] = value
        return out

    @staticmethod
    def _call_names(expr: ast.AST, var_exprs: Dict[str, ast.AST]) -> Set[str]:
        """Terminal callee names reachable from ``expr``, following local
        Name references through ``var_exprs`` a few levels deep."""
        names: Set[str] = set()
        seen: Set[int] = set()
        stack: List[Tuple[ast.AST, int]] = [(expr, 0)]
        while stack:
            node, depth = stack.pop()
            if id(node) in seen:
                continue
            seen.add(id(node))
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    names.add(_last(_dotted(sub.func)))
                elif (
                    isinstance(sub, ast.Name)
                    and depth < 3
                    and sub.id in var_exprs
                ):
                    stack.append((var_exprs[sub.id], depth + 1))
        return names

    def check(self, ctx: LintContext) -> List[Finding]:
        out = []
        var_exprs = self._var_exprs(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not _is_jit_call(node):
                continue
            if not _has_kwarg(node, "donate_argnums", "donate_argnames"):
                continue
            if _has_splat(node):
                continue
            kw = next(
                (k for k in node.keywords if k.arg == "out_shardings"), None
            )
            if kw is None:
                out.append(
                    self.finding(
                        ctx,
                        node,
                        "jit with donate_argnums but no out_shardings: "
                        "donated carries decay to jit-chosen (usually "
                        "replicated) layouts; pass plan-derived "
                        "out_shardings (ShardingPlan.shardings_for) or "
                        "forward **kwargs",
                    )
                )
                continue
            callees = self._call_names(kw.value, var_exprs)
            if callees & _PLAN_SOURCES:
                continue  # cites the plan (or a projection of it)
            if "NamedSharding" in callees:
                out.append(
                    self.finding(
                        ctx,
                        node,
                        "hand-built NamedSharding in a donated jit's "
                        "out_shardings: derive the carry layouts from the "
                        "plan (ShardingPlan.shardings_for / "
                        "donated_carry_shardings) so the placement the "
                        "step pins is the one the comm audit and ledger "
                        "counters price (plan == audit == counters)",
                    )
                )
        return out


_NP_STATEFUL = {
    "seed",
    "rand",
    "randn",
    "random",
    "normal",
    "uniform",
    "randint",
    "permutation",
    "choice",
    "shuffle",
    "standard_normal",
}


class StatefulRngOutsideCounterStream(Rule):
    """TDX102 — ad-hoc RNG outside ``utils/rng.py``.

    Convention: parameter init draws keys from utils/rng.py's counter
    stream — same seed => bit-identical deferred vs eager init.  A raw
    ``jax.random.PRNGKey`` or global-generator ``np.random.*`` call
    creates a parallel seed universe that breaks that identity.
    Seeded generators (``np.random.RandomState(s)``,
    ``np.random.default_rng(s)``) are fine: they are explicit streams.
    """

    rule_id = "TDX102"
    severity = "error"
    summary = "stateful RNG outside utils/rng.py counter stream"

    def check(self, ctx: LintContext) -> List[Finding]:
        if ctx.rel_path.endswith("utils/rng.py"):
            return []
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func) or ""
            if _last(name) == "PRNGKey" or name == "jax.random.key":
                out.append(
                    self.finding(
                        ctx,
                        node,
                        "raw %s: draw keys from utils/rng.py's counter "
                        "stream (next_rng_key) so deferred and eager init "
                        "stay bit-identical" % (name or "PRNGKey"),
                    )
                )
                continue
            parts = name.split(".")
            if (
                len(parts) == 3
                and parts[0] in ("np", "numpy")
                and parts[1] == "random"
                and parts[2] in _NP_STATEFUL
            ):
                out.append(
                    self.finding(
                        ctx,
                        node,
                        "global-generator %s: use a seeded "
                        "np.random.RandomState/default_rng or the "
                        "utils/rng.py counter stream" % name,
                    )
                )
        return out


_RAW_COLLECTIVES = {
    "psum",
    "pmean",
    "pmax",
    "pmin",
    "all_gather",
    "ppermute",
    "pshuffle",
    "all_to_all",
    "psum_scatter",
}


def _contains_booking_call(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            callee = _last(_dotted(node.func))
            if callee == "record_collective" or callee.startswith("_record"):
                return True
    return False


class RawCollectiveOutsideChokePoint(Rule):
    """TDX103 — raw ``lax`` collective invisible to the comm audit.

    Convention: collectives route through parallel/collectives.py (or
    book themselves via obs.comm.record_collective) so the closed-form
    wire model in obs/comm.py stays COMPLETE — an unbooked collective
    makes every comm-audit pin an undercount.  A raw lax call is exempt
    only when a lexically enclosing function also books the traffic
    (calls record_collective or a ``_record*`` helper), which is how
    scan-body collectives with static trip counts are accounted.
    """

    rule_id = "TDX103"
    severity = "error"
    summary = "raw lax collective outside parallel/collectives.py"

    def check(self, ctx: LintContext) -> List[Finding]:
        if ctx.rel_path.endswith("parallel/collectives.py"):
            return []
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func) or ""
            parts = name.split(".")
            if not (
                parts[-1] in _RAW_COLLECTIVES
                and len(parts) >= 2
                and parts[-2] == "lax"
            ):
                continue
            if any(
                _contains_booking_call(fn)
                for fn in ctx.enclosing_functions(node)
            ):
                continue
            out.append(
                self.finding(
                    ctx,
                    node,
                    "raw lax.%s bypasses parallel/collectives.py: the "
                    "obs/comm.py audit cannot see it, so comm pins "
                    "undercount wire bytes — use the choke-point wrapper "
                    "or book it with record_collective in the enclosing "
                    "function" % parts[-1],
                )
            )
        return out


_CONTROL_FLOW = {
    "scan",
    "while_loop",
    "fori_loop",
    "cond",
    "switch",
    "associative_scan",
}
_HOST_SYNC_NP = {"np.asarray", "numpy.asarray", "np.array", "numpy.array"}


class HostSyncInCompiledBody(Rule):
    """TDX104 — host synchronisation lexically inside compiled code.

    Convention: decode/train loop bodies never host-sync (the PR 6
    persistent-loop lesson: one stray ``.item()`` serialises the whole
    pipeline on the host).  "Compiled" = decorated with jit/pmap, or
    passed by name (or inline lambda) to lax.scan/while_loop/fori_loop/
    cond/switch.
    """

    rule_id = "TDX104"
    severity = "warning"
    summary = "host sync (float/.item/np.asarray/block_until_ready) in compiled body"

    def _compiled_defs(self, ctx: LintContext) -> List[ast.AST]:
        compiled_names: Set[str] = set()
        compiled_lambdas: List[ast.Lambda] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if _last(_dotted(node.func)) not in _CONTROL_FLOW:
                continue
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    compiled_names.add(arg.id)
                elif isinstance(arg, ast.Lambda):
                    compiled_lambdas.append(arg)
        defs: List[ast.AST] = list(compiled_lambdas)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if node.name in compiled_names:
                defs.append(node)
                continue
            for dec in node.decorator_list:
                if (
                    _dotted(dec) in _JIT_NAMES
                    or (isinstance(dec, ast.Call) and _is_jit_call(dec))
                ):
                    defs.append(node)
                    break
        return defs

    def check(self, ctx: LintContext) -> List[Finding]:
        out = []
        seen: Set[Tuple[int, int]] = set()
        for fn in self._compiled_defs(ctx):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                loc = (node.lineno, node.col_offset)
                if loc in seen:
                    continue
                name = _dotted(node.func) or ""
                label = None
                if (
                    isinstance(node.func, ast.Name)
                    and node.func.id == "float"
                    and node.args
                    and not isinstance(node.args[0], ast.Constant)
                ):
                    label = "float() on a traced value"
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and not node.args
                ):
                    label = ".item()"
                elif name in _HOST_SYNC_NP:
                    label = name + "()"
                elif _last(name) == "block_until_ready":
                    label = "block_until_ready()"
                if label is None:
                    continue
                seen.add(loc)
                out.append(
                    self.finding(
                        ctx,
                        node,
                        "%s inside a compiled body forces a device->host "
                        "sync on every trace/step — hoist it outside the "
                        "jit/scan boundary" % label,
                    )
                )
        return out


_REG_METHODS = {"counter", "gauge", "summary"}


def _neg_literal(node: ast.AST) -> bool:
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return isinstance(node.operand, ast.Constant)
    return isinstance(node, ast.Constant) and isinstance(
        node.value, (int, float)
    ) and node.value < 0


class MetricsRegistryMisuse(Rule):
    """TDX105 — metrics contract violations.

    (a) Counters are monotone: ``Counter.inc`` raises on negative at
    runtime; ``.set``/``.dec`` on a counter handle doesn't exist and
    fails only when first executed.  Catch it statically.
    (b) A ``tdx_*`` MetricFamily emitted with a literal name that no
    registry ever registers (and whose ``tdx_<component>`` prefix no
    collector declares) scrapes as a ghost series no dashboard knows.
    """

    rule_id = "TDX105"
    severity = "error"
    summary = "counter decrement/set, or unregistered tdx_* metric family"

    def collect(self, ctx: LintContext) -> None:
        names: Set[str] = ctx.shared.setdefault(  # type: ignore[assignment]
            "TDX105.names", set()
        )
        prefixes: Set[str] = ctx.shared.setdefault(  # type: ignore[assignment]
            "TDX105.prefixes", set()
        )
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _REG_METHODS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                ):
                    names.add(node.args[0].value)
                for kw in node.keywords:
                    if (
                        kw.arg == "prefix"
                        and isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, str)
                    ):
                        prefixes.add(kw.value.value)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                all_args = args.posonlyargs + args.args + args.kwonlyargs
                defaults = args.defaults + args.kw_defaults
                # align defaults right-to-left over positional args
                pos = args.posonlyargs + args.args
                for a, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
                    if (
                        a.arg == "prefix"
                        and isinstance(d, ast.Constant)
                        and isinstance(d.value, str)
                    ):
                        prefixes.add(d.value)
                for a, d in zip(args.kwonlyargs, args.kw_defaults):
                    if (
                        d is not None
                        and a.arg == "prefix"
                        and isinstance(d, ast.Constant)
                        and isinstance(d.value, str)
                    ):
                        prefixes.add(d.value)
                del all_args, defaults

    def _counter_vars(self, ctx: LintContext) -> Set[str]:
        out: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Assign):
                continue
            val = node.value
            if not isinstance(val, ast.Call):
                continue
            is_counter = (
                isinstance(val.func, ast.Attribute)
                and val.func.attr == "counter"
            ) or _dotted(val.func) in ("Counter", "metrics.Counter")
            if not is_counter:
                continue
            for tgt in node.targets:
                d = _dotted(tgt)
                if d:
                    out.add(d)
        return out

    def check(self, ctx: LintContext) -> List[Finding]:
        out = []
        counter_vars = self._counter_vars(ctx)
        names: Set[str] = ctx.shared.get("TDX105.names", set())  # type: ignore[assignment]
        prefixes: Set[str] = ctx.shared.get("TDX105.prefixes", set())  # type: ignore[assignment]
        roots = {p for p in prefixes} | {
            "_".join(n.split("_")[:2]) for n in names | prefixes
        }
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute):
                base = _dotted(node.func.value)
                if base in counter_vars:
                    if node.func.attr in ("set", "dec"):
                        out.append(
                            self.finding(
                                ctx,
                                node,
                                "counter %s.%s(): counters are monotone — "
                                "Counter only has inc(); use a Gauge for "
                                "set/dec semantics" % (base, node.func.attr),
                            )
                        )
                        continue
                    if node.func.attr == "inc" and node.args and _neg_literal(
                        node.args[0]
                    ):
                        out.append(
                            self.finding(
                                ctx,
                                node,
                                "counter %s.inc(negative): Counter.inc "
                                "raises on negative amounts at runtime"
                                % base,
                            )
                        )
                        continue
            if (
                _last(_dotted(node.func)) == "MetricFamily"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                mname = node.args[0].value
                if not mname.startswith("tdx_"):
                    continue
                root = "_".join(mname.split("_")[:2])
                if mname in names or root in roots:
                    continue
                out.append(
                    self.finding(
                        ctx,
                        node,
                        "MetricFamily(%r) emits a tdx_* series that no "
                        "registry registers and no collector prefix "
                        "declares — ghost metric" % mname,
                    )
                )
        return out


_WALL_CLOCKS = {
    "time.time",
    "time.time_ns",
    "time.perf_counter",
    "time.perf_counter_ns",
    "time.monotonic",
    "time.monotonic_ns",
}


class NondeterminismInCounterRows(Rule):
    """TDX106 — nondeterministic inputs near exact-gated counter rows.

    Convention: ledger rows with ``metric_class="counter"`` compare
    EXACTLY across runs in the perf gate (PR 7) — a wall-clock read or a
    set-iteration order feeding one makes the gate flap.  Flagged inside
    any function that creates counter-class rows.
    """

    rule_id = "TDX106"
    severity = "warning"
    summary = "wall-clock or set-iteration in a counter-row-producing function"

    def _makes_counter_rows(self, fn: ast.AST) -> bool:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            for kw in node.keywords:
                if (
                    kw.arg == "metric_class"
                    and isinstance(kw.value, ast.Constant)
                    and kw.value.value == "counter"
                ):
                    return True
            if _last(_dotted(node.func)) in ("make_row", "counter_row") and any(
                isinstance(a, ast.Constant) and a.value == "counter"
                for a in node.args
            ):
                return True
        return False

    def check(self, ctx: LintContext) -> List[Finding]:
        out = []
        seen: Set[Tuple[int, int]] = set()
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not self._makes_counter_rows(fn):
                continue
            for node in ast.walk(fn):
                loc = (getattr(node, "lineno", 0), getattr(node, "col_offset", 0))
                if isinstance(node, ast.Call):
                    name = _dotted(node.func) or ""
                    if name in _WALL_CLOCKS or name.endswith("datetime.now"):
                        if loc in seen:
                            continue
                        seen.add(loc)
                        out.append(
                            self.finding(
                                ctx,
                                node,
                                "%s() in a function producing "
                                "metric_class='counter' rows: counter rows "
                                "gate EXACTLY — derive values from counted "
                                "events, keep wall clocks out or move them "
                                "to timing-band rows" % name,
                            )
                        )
                elif isinstance(node, (ast.For, ast.comprehension)):
                    it = node.iter
                    if isinstance(it, ast.Set) or (
                        isinstance(it, ast.Call)
                        and isinstance(it.func, ast.Name)
                        and it.func.id == "set"
                    ):
                        if loc in seen:
                            continue
                        seen.add(loc)
                        out.append(
                            self.finding(
                                ctx,
                                node,
                                "iterating a set in a function producing "
                                "counter rows: set order is "
                                "hash-randomised — sort it first",
                            )
                        )
        return out


def default_rules() -> List[Rule]:
    return [
        DonatedJitNeedsOutShardings(),
        StatefulRngOutsideCounterStream(),
        RawCollectiveOutsideChokePoint(),
        HostSyncInCompiledBody(),
        MetricsRegistryMisuse(),
        NondeterminismInCounterRows(),
    ]


#: id -> (severity, one-line summary); TDX100 is emitted by the core.
RULE_CATALOG: Dict[str, Tuple[str, str]] = {
    "TDX100": ("error", "tdx-lint suppression without justification text"),
    **{
        r.rule_id: (r.severity, r.summary)
        for r in default_rules()
    },
}
