"""What a block's ``jax.checkpoint`` keeps for the backward.

A model names a policy per block (``LlamaConfig.remat_policy``):

  ``"full"`` — keep nothing; the backward recomputes the block's whole
  forward (``jax.checkpoint``'s default, the smallest footprint).
  ``"dots"`` — keep the block's matmul outputs
  (``jax.checkpoint_policies.dots_with_no_batch_dims_saveable``) and the
  residuals the flash kernel's forward names; the backward recomputes
  only elementwise work from them.
  ``"auto"`` — whichever :func:`remat_scope` says.  Only the code that
  compiles the step sees the device's memory, so it decides
  (``parallel.fsdp.ShardedTrainStep`` compiles the saving program and
  keeps it when it fits); outside any scope ``"auto"`` is ``"full"``.

The scope is read while a step is TRACED: a jitted step must set it
inside the traced function (one jitted callable per choice), since
``jax.jit`` does not key its cache on it.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, Optional

import jax

from ..ops.flash_attention import RESIDUAL_NAMES

__all__ = ["POLICIES", "RematScope", "remat_scope", "checkpoint_policy"]

POLICIES = ("auto", "full", "dots")

_SAVE_DOTS = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES),
)


class RematScope:
    """What ``"auto"`` means inside the scope, and every policy name the
    blocks traced inside it asked for (``named``)."""

    def __init__(self, auto: str) -> None:
        if auto not in ("full", "dots"):
            raise ValueError(f"remat_scope takes 'full' or 'dots', got {auto!r}")
        self.auto = auto
        self.named: set = set()


_SCOPE: contextvars.ContextVar[Optional[RematScope]] = contextvars.ContextVar(
    "tdx_remat_scope", default=None
)


@contextlib.contextmanager
def remat_scope(auto: str) -> Iterator[RematScope]:
    """Blocks traced inside resolve ``"auto"`` to ``auto``; the yielded
    scope records the policies they named."""
    token = _SCOPE.set(RematScope(auto))
    try:
        yield _SCOPE.get()
    finally:
        _SCOPE.reset(token)


def checkpoint_policy(name: str):
    """The ``jax.checkpoint`` policy for ``name`` (None = save nothing)."""
    if name not in POLICIES:
        raise ValueError(
            f"remat_policy must be one of {POLICIES}, got {name!r}"
        )
    scope = _SCOPE.get()
    if scope is not None:
        scope.named.add(name)
    if name == "auto":
        name = scope.auto if scope is not None else "full"
    if name == "full":
        return None
    return _SAVE_DOTS
