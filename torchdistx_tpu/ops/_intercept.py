"""Context-scoped interception of the public ``jax.numpy`` / ``jax.random``
surface — closing the fake-mode escape hatch.

The reference's Fake key is a dispatcher *catch-all* (reference
src/cc/torchdistx/fake.cc:546-548): inside ``fake_mode()`` nothing can
allocate, and ops on fake tensors are intercepted even *outside* the mode
because the Fake key lives in the tensor's own dispatch key set.  JAX has
no dispatcher to hook, so the public ``jnp`` namespace is patched (once,
on first fake/deferred entry, then left installed): a call whose arguments
contain a :class:`FakeArray` — in or out of the mode, mirroring the
key-set behavior — or a *creation* call made by a thread inside fake mode,
routes through :func:`ops.apply_op` (shape propagation / recording);
everything else passes straight through to the original with only a cheap
argument scan.

``jax.nn.initializers`` is covered at its *call-time globals*: initializer
closures (``glorot_uniform()``'s returned ``init``) resolve ``random.X`` /
``jnp.X`` from ``jax._src.nn.initializers``'s module dict on every call, so
interposing those two module attributes catches every initializer — even
closures created before the patch (e.g. third-party defaults captured at
import, like flax's ``default_kernel_init``), which a patch of the public
``jax.nn.initializers`` namespace would miss.

Scope and limitations (documented divergence from a true dispatcher hook):
  - only attribute lookups through the module namespace are intercepted;
    references captured *before* the patch (``from jax.numpy import zeros``)
    and non-jnp entry points (``jax.nn.relu``) escape it — a fake argument
    there surfaces JAX's invalid-type error whose repr shows ``fake=True``;
  - ``jax.random`` key plumbing (``PRNGKey``/``key``/``split``/``fold_in``)
    is never faked — keys stay real so the counter-based RNG stream
    (utils/rng.py) keeps deferred/eager init bit-identical.  It IS wrapped,
    to suspend the mode around the call: this jax's internals resolve the
    patched public ``jnp``, so an unwrapped ``PRNGKey(0)`` under the mode
    would have its internal coercions faked (see _RANDOM_KEY_PLUMBING);
  - creation calls inside an active jax trace (jit/grad) are not faked:
    returning a FakeArray into a tracer would corrupt the trace.
"""

from __future__ import annotations

import functools
import threading
import types
from typing import Any, Callable

import jax
import jax.numpy as jnp

__all__ = ["ensure_installed", "uninstall"]

# jnp functions that allocate from nothing (the reference's "factory ops",
# fake.cc:462-464: ops with no tensor args get faked under the mode).
_JNP_CREATION = {
    "array",
    "asarray",
    "ascontiguousarray",
    "zeros",
    "ones",
    "empty",
    "full",
    "zeros_like",
    "ones_like",
    "empty_like",
    "full_like",
    "arange",
    "linspace",
    "logspace",
    "geomspace",
    "eye",
    "identity",
    "tri",
    "frombuffer",
    "fromfunction",
    "fromiter",
}

# Metadata-only functions are never interposed: they read shape/dtype
# attributes, which FakeArray provides, and routing them through eval_shape
# would abstract their static int/dtype outputs into avals.
_METADATA_PASSTHROUGH = {
    "shape",
    "ndim",
    "size",
    "result_type",
    "promote_types",
    "issubdtype",
    "isdtype",
    "iscomplexobj",
    "isrealobj",
    "isscalar",
    "can_cast",
    "save",
    "savez",
    "load",
    "dtype",
    "broadcast_shapes",
    "get_printoptions",
    "set_printoptions",
    "printoptions",
}

# jax.random key plumbing: never faked — keys stay real so the
# counter-based RNG stream (utils/rng.py) keeps deferred/eager init
# bit-identical.  Their INTERNALS resolve the
# patched public ``jax.numpy`` (jax._src.random does ``import jax.numpy
# as jnp``), so "not intercepting" them is not enough: a bare
# ``PRNGKey(0)`` under the mode would have its internal ``jnp.asarray``
# coercions faked.  They are wrapped to SUSPEND the mode for the
# duration of the call instead.
_RANDOM_KEY_PLUMBING = {
    "PRNGKey",
    "key",
    "split",
    "fold_in",
    "key_data",
    "wrap_key_data",
    "clone",
    "key_impl",
}

# jax.random samplers (factory ops keyed by a real PRNG key).
_RANDOM_CREATION = {
    "bits",
    "normal",
    "uniform",
    "truncated_normal",
    "bernoulli",
    "randint",
    "gumbel",
    "exponential",
    "laplace",
    "logistic",
    "cauchy",
    "gamma",
    "beta",
    "chisquare",
    "dirichlet",
    "poisson",
    "rademacher",
    "maxwell",
    "pareto",
    "t",
    "ball",
    "orthogonal",
    "loggamma",
    "categorical",
    "choice",
    "permutation",
    "multivariate_normal",
    "double_sided_maxwell",
    "weibull_min",
}


def _has_fake(values) -> bool:
    from ..fake import FakeArray

    for v in values:
        if isinstance(v, FakeArray):
            return True
        if isinstance(v, (list, tuple)):
            for w in v:
                if isinstance(w, FakeArray):
                    return True
    return False


def _trace_clean() -> bool:
    try:
        from jax._src import core as _core

        return _core.trace_state_clean()
    except Exception:
        return True


def _make_wrapper(name: str, orig: Callable[..., Any], creation: bool):
    from ..fake import in_fake_mode

    @functools.wraps(orig)
    def wrapper(*args, **kwargs):
        from . import apply_op

        if _has_fake(args) or _has_fake(kwargs.values()):
            return apply_op(orig, *args, op_name=name, **kwargs)
        if creation and in_fake_mode() and _trace_clean():
            return apply_op(orig, *args, op_name=name, **kwargs)
        return orig(*args, **kwargs)

    wrapper.__wrapped_original__ = orig  # uninstall marker
    return wrapper


def _make_key_plumbing_wrapper(orig: Callable[..., Any]):
    """Run a jax.random key-plumbing fn with the fake/deferred mode
    suspended: its output must be a real key, and its internal jnp
    coercions must not be faked (see _RANDOM_KEY_PLUMBING)."""
    from ..fake import in_fake_mode

    @functools.wraps(orig)
    def wrapper(*args, **kwargs):
        if (in_fake_mode() and _trace_clean()
                and not (_has_fake(args) or _has_fake(kwargs.values()))):
            from ..fake import no_deferred_init

            with no_deferred_init():
                return orig(*args, **kwargs)
        return orig(*args, **kwargs)

    wrapper.__wrapped_original__ = orig
    return wrapper


class _InterposedUfunc:
    """Callable proxy for ``jnp.ufunc`` objects (``add``, ``maximum``, ...):
    interposes ``__call__`` while delegating every other attribute —
    ``.at``, ``.reduce``, ``.accumulate``, ``.outer`` — to the original, so
    the ufunc method surface survives the patch."""

    def __init__(self, call_wrapper: Callable[..., Any], orig: Any) -> None:
        self.__dict__["_call_wrapper"] = call_wrapper
        self.__dict__["__wrapped_original__"] = orig

    def __call__(self, *args, **kwargs):
        return self._call_wrapper(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.__dict__["__wrapped_original__"], name)

    def __repr__(self) -> str:
        return repr(self.__dict__["__wrapped_original__"])


def _is_ufunc_like(obj: Any) -> bool:
    return hasattr(obj, "at") and hasattr(obj, "reduce") and callable(obj)


def _wrappable(obj: Any) -> bool:
    if isinstance(obj, (type, types.ModuleType)):
        return False
    if hasattr(obj, "__wrapped_original__"):
        return False  # already patched
    return callable(obj)


def _wrap_callable(label: str, orig: Any, is_creation: bool) -> Any:
    """The one wrap decision shared by the public-namespace patch and
    ``_ModuleProxy``: fake-aware dispatch wrapper, ufunc-protocol shim on
    top where the original is ufunc-like."""
    wrapper = _make_wrapper(label, orig, is_creation)
    if _is_ufunc_like(orig):
        wrapper = _InterposedUfunc(wrapper, orig)
    return wrapper


class _ModuleProxy:
    """Interposing stand-in for a module referenced from another module's
    globals (``jax._src.nn.initializers``'s ``random`` and ``jnp``).

    Attribute access returns the original attribute wrapped with the same
    fake-aware dispatch as the public-namespace patch: fake args or a
    creation call under the mode route through ``apply_op``; everything
    else passes through.  Submodules (``jnp.linalg``) proxy recursively so
    e.g. the ``orthogonal`` initializer's ``jnp.linalg.qr`` propagates
    fakes instead of raising JAX's invalid-type error.

    Wrappers are cached per (name, underlying object identity): attribute
    resolution stays LIVE — rebinding ``jax.random.uniform`` (a test
    monkeypatch, say) after the proxy has been used invalidates the cached
    wrapper, matching the behavior every non-proxied caller sees.
    """

    def __init__(self, mod: Any, creation: set, label: str) -> None:
        self.__dict__["__wrapped_original__"] = mod
        self.__dict__["_creation"] = creation
        self.__dict__["_label"] = label
        self.__dict__["_cache"] = {}

    def __getattr__(self, name: str) -> Any:
        mod = self.__dict__["__wrapped_original__"]
        orig = getattr(mod, name)
        cache = self.__dict__["_cache"]
        hit = cache.get(name)
        if hit is not None and hit[0] is orig:
            return hit[1]
        if name in _METADATA_PASSTHROUGH:
            # same invariant as the public patch: metadata fns must keep
            # their static int/dtype outputs, never abstract into avals
            out: Any = orig
        elif isinstance(orig, types.ModuleType):
            out = _ModuleProxy(
                orig,
                self.__dict__["_creation"],
                f"{self.__dict__['_label']}.{name}",
            )
        elif _wrappable(orig):
            out = _wrap_callable(
                f"{self.__dict__['_label']}.{name}",
                orig,
                name in self.__dict__["_creation"],
            )
        else:
            out = orig
        cache[name] = (orig, out)
        return out

    def __repr__(self) -> str:
        return f"<interposed {self.__dict__['__wrapped_original__']!r}>"


class _Patcher:
    """Installs the wrappers once and leaves them in place: a FakeArray can
    outlive the context that created it, and parity requires ops on it to
    stay intercepted after the mode exits (the reference keeps the Fake key
    in the tensor's key set; mode state is TLS but handler registration is
    global — fake.cc:554,588,546-548).  ``uninstall`` exists for tests."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._installed = False
        self._saved: list[tuple[Any, str, Any]] = []

    def ensure_installed(self) -> None:
        with self._lock:
            if self._installed:
                return
            self._installed = True
            for name in dir(jnp):
                if name.startswith("_") or name in _METADATA_PASSTHROUGH:
                    continue
                orig = getattr(jnp, name, None)
                if orig is None or not _wrappable(orig):
                    continue
                wrapper = _wrap_callable(name, orig, name in _JNP_CREATION)
                self._saved.append((jnp, name, orig))
                setattr(jnp, name, wrapper)
            for name in _RANDOM_CREATION:
                orig = getattr(jax.random, name, None)
                if orig is None or not _wrappable(orig):
                    continue
                wrapper = _wrap_callable(f"random_{name}", orig, True)
                self._saved.append((jax.random, name, orig))
                setattr(jax.random, name, wrapper)
            for name in _RANDOM_KEY_PLUMBING:
                orig = getattr(jax.random, name, None)
                if orig is None or not _wrappable(orig):
                    continue
                self._saved.append((jax.random, name, orig))
                setattr(jax.random, name, _make_key_plumbing_wrapper(orig))
            # jax.nn activations (relu/gelu/softmax/...): two-level coverage.
            # Level 1 — the public namespace, so attribute-style calls
            # (``jax.nn.gelu(fake)``) fake-propagate instead of leaking a
            # raw JAX type error.  None are creation ops: they all take an
            # array argument, so the fake-arg scan is the trigger.
            import jax.nn as _jax_nn

            for name in dir(_jax_nn):
                if name.startswith("_"):
                    continue
                orig = getattr(_jax_nn, name, None)
                if orig is None or not _wrappable(orig):
                    continue
                wrapper = _wrap_callable(f"nn.{name}", orig, False)
                self._saved.append((_jax_nn, name, orig))
                setattr(_jax_nn, name, wrapper)
            # Level 2 — the internal functions module's call-time globals
            # (``jnp``/``lax``), so references captured BEFORE the patch
            # (``from jax.nn import relu`` at user-module import, which
            # typically precedes the first fake/deferred entry) are still
            # covered: the captured function body resolves ``jnp.maximum``
            # etc. from these module globals on every call — the same
            # trick as the initializers coverage below.
            try:
                from jax._src.nn import functions as _nn_internal
            except ImportError:  # jax layout changed: public patch only
                _nn_internal = None
            if _nn_internal is not None:
                # numpy_util is proxied too: bodies validate/promote via
                # numpy_util.promote_args_inexact(name, x) BEFORE any jnp
                # op, and that helper type-rejects a FakeArray.  Through
                # the proxy it routes apply_op (string arg rides the
                # static template), so promotion shape-propagates.
                for attr, creation in (("jnp", _JNP_CREATION),
                                       ("lax", set()),
                                       ("numpy_util", set())):
                    target = getattr(_nn_internal, attr, None)
                    if not isinstance(target, types.ModuleType):
                        continue
                    self._saved.append((_nn_internal, attr, target))
                    setattr(
                        _nn_internal,
                        attr,
                        _ModuleProxy(target, creation, f"nn.{attr}"),
                    )
            # Level 3 — custom_jvp/custom_vjp __call__ (class-level): relu
            # and friends are custom-derivative OBJECTS whose __call__
            # type-rejects a FakeArray before the body (and its patched
            # globals) ever run.  Hooking the class catches every
            # custom-derivative callable — including third-party ones —
            # which is the closest JAX analog of the reference's
            # dispatcher catch-all.  eval_shape traces the object fine,
            # so apply_op needs no special casing.
            try:
                from jax._src import custom_derivatives as _cd
            except ImportError:
                _cd = None
            if _cd is not None:
                for cls_name in ("custom_jvp", "custom_vjp"):
                    cls = getattr(_cd, cls_name, None)
                    if cls is None:
                        continue
                    orig_call = cls.__call__
                    if hasattr(orig_call, "__wrapped_original__"):
                        continue

                    def _make_call(orig_call):
                        @functools.wraps(orig_call)
                        def call(self, *args, **kwargs):
                            if _has_fake(args) or _has_fake(kwargs.values()):
                                from . import apply_op

                                name = getattr(
                                    getattr(self, "fun", None),
                                    "__name__",
                                    "custom_derivative_call",
                                )
                                return apply_op(
                                    functools.partial(orig_call, self),
                                    *args,
                                    op_name=name,
                                    **kwargs,
                                )
                            return orig_call(self, *args, **kwargs)

                        call.__wrapped_original__ = orig_call
                        return call

                    self._saved.append((cls, "__call__", orig_call))
                    setattr(cls, "__call__", _make_call(orig_call))
            # jax.nn.initializers: interpose the internal module's call-time
            # globals so every initializer closure is covered regardless of
            # when it was created (see module docstring).  Samplers are
            # creation ops (a real key in, an array out); jnp creation
            # names mirror the public patch (covers the zeros/ones
            # initializers).
            try:
                from jax._src.nn import initializers as _ini_internal
            except ImportError:  # jax layout changed: public patch only
                _ini_internal = None
            if _ini_internal is not None:
                for attr, target, creation in (
                    ("random", getattr(_ini_internal, "random", None),
                     _RANDOM_CREATION),
                    ("jnp", getattr(_ini_internal, "jnp", None),
                     _JNP_CREATION),
                    # orthogonal()'s body also resolves ``lax`` from these
                    # globals (lax.broadcast_to_rank on the QR sign fix-up)
                    ("lax", getattr(_ini_internal, "lax", None),
                     set()),
                ):
                    if not isinstance(target, types.ModuleType):
                        continue
                    self._saved.append((_ini_internal, attr, target))
                    setattr(
                        _ini_internal,
                        attr,
                        _ModuleProxy(target, creation, attr),
                    )

    def uninstall(self) -> None:
        with self._lock:
            if not self._installed:
                return
            self._installed = False
            for mod, name, orig in self._saved:
                setattr(mod, name, orig)
            self._saved.clear()


_patcher = _Patcher()
ensure_installed = _patcher.ensure_installed
uninstall = _patcher.uninstall
