"""Weight-only int8 quantization for inference.

Autoregressive decode is weight-READ-bound: every generated token streams
the full parameter set from HBM while the matmuls are tiny (batch x 1
activations).  Storing Linear weights as int8 with one f32 scale per
output channel cuts that traffic 2x vs bf16 (4x vs f32) at ~0.4% RMS
weight error (per-channel absmax), which is the standard weight-only
recipe (AWQ/GPTQ-class methods start from exactly this storage format).

The dequantize is folded AFTER the matmul: ``y = (x @ W_q^T) * scale``
with the int8->compute-dtype convert of ``W_q`` fused into the dot by
XLA — the scale multiply is O(out) per row, not O(out * in).

Quantize AFTER materialization (real arrays in, real arrays out):

    model = tdx.deferred_init(Llama.from_name, "llama2_7b")
    tdx.materialize_module(model)
    quantize_module(model)           # Linears -> QuantizedLinear in place

``state_dict``/``named_parameters`` carry the int8 codes + scales, so
checkpointing a quantized model stores the small format.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .layers import Linear
from .module import Module, Parameter
from .moe import MoE

__all__ = ["QuantizedLinear", "QuantizedMoE", "quantize_module"]


class QuantizedLinear(Module):
    """Linear with int8 weight codes and a per-output-channel f32 scale.

    Built from an existing :class:`Linear` via :meth:`from_linear`; the
    forward computes in the input's dtype with the dequant scale applied
    to the matmul output.
    """

    _keep_dtype = ("scale",)  # Module.to(bf16) must not degrade the scale

    def __init__(self, weight_q, scale, bias=None) -> None:
        super().__init__()
        self.in_features = weight_q.shape[1]
        self.out_features = weight_q.shape[0]
        self.weight_q = Parameter(weight_q)  # (out, in) int8
        self.scale = Parameter(scale)  # (out,) f32
        if bias is not None:
            self.bias = Parameter(bias)
        else:
            self.register_parameter("bias", None)

    @classmethod
    def from_linear(cls, lin: Linear) -> "QuantizedLinear":
        w = jnp.asarray(lin.weight, jnp.float32)  # (out, in)
        absmax = jnp.max(jnp.abs(w), axis=1, keepdims=True)  # per out-chan
        scale = jnp.maximum(absmax / 127.0, 1e-30)
        w_q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
        return cls(
            w_q,
            scale[:, 0].astype(jnp.float32),
            None if lin.bias is None else lin.bias,
        )

    def forward(self, x):
        y = x @ self.weight_q.astype(x.dtype).T
        # scale applied in f32 (free under jit): scale.astype(bf16) would
        # add up to ~0.39% systematic per-channel error on top of the
        # ~0.4% quantization RMS
        y = (y.astype(jnp.float32) * self.scale).astype(x.dtype)
        if self.bias is not None:
            y = y + self.bias.astype(x.dtype)
        return y

    def __repr__(self) -> str:  # mirrors Linear's repr convention
        return (
            f"QuantizedLinear(in_features={self.in_features}, "
            f"out_features={self.out_features}, "
            f"bias={self.bias is not None}, int8)"
        )


def _quantize_stacked(w, out_axis):
    """(E, ., .) stacked expert weight -> int8 codes + per-(expert,
    out-channel) f32 scale shaped to broadcast over the OUTPUT of the
    expert einsum (scale applied post-contraction, like QuantizedLinear).
    """
    w = jnp.asarray(w, jnp.float32)
    reduce_axis = 3 - out_axis  # the contracted dim of (E, d0, d1)
    absmax = jnp.max(jnp.abs(w), axis=reduce_axis, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-30)
    codes = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return codes, jnp.squeeze(scale, reduce_axis)  # (E, out)


class QuantizedMoE(MoE):
    """MoE whose stacked expert weights live as int8 + per-(expert,
    out-channel) scales — the >95%-of-bytes case quantize_module would
    otherwise silently skip on Mixtral-class models.  Routing, capacity
    slotting, and both dispatch modes are inherited; only the expert
    einsums change (scale folded after each contraction, f32)."""

    _keep_dtype = ("s_gate", "s_up", "s_down")

    @classmethod
    def from_moe(cls, m: MoE) -> "QuantizedMoE":
        unsupported = {
            "dispatch_mode='grouped'": m.dispatch_mode == "grouped",
            "scoring='sigmoid'": m.scoring != "softmax",
            "a selection bias": m.e_score_correction_bias is not None,
            "a shared expert": m.shared is not None,
        }
        bad = [name for name, has in unsupported.items() if has]
        if bad:
            raise ValueError(
                f"QuantizedMoE does not support {', '.join(bad)}: only the "
                "softmax router over the dense and capacity paths has int8 "
                "expert einsums"
            )
        q = cls.__new__(cls)
        Module.__init__(q)
        for attr in ("dim", "ffn_dim", "n_experts", "top_k",
                     "capacity_factor", "dispatch_mode", "scoring",
                     "routed_scale", "use_kernel"):
            object.__setattr__(q, attr, getattr(m, attr))
        q.register_parameter("e_score_correction_bias", None)
        q.shared = None
        q.router = QuantizedLinear.from_linear(m.router)
        wg, sg = _quantize_stacked(m.w_gate, out_axis=2)  # (E, D, F)
        wu, su = _quantize_stacked(m.w_up, out_axis=2)
        wd, sd = _quantize_stacked(m.w_down, out_axis=2)  # (E, F, D)
        q.w_gate, q.s_gate = Parameter(wg), Parameter(sg)
        q.w_up, q.s_up = Parameter(wu), Parameter(su)
        q.w_down, q.s_down = Parameter(wd), Parameter(sd)
        return q

    def _deq_ein(self, eq, x, w_q, scale):
        y = jnp.einsum(eq, x, w_q.astype(x.dtype))
        return (y.astype(jnp.float32) * scale).astype(x.dtype)

    def _experts(self, expert_in):
        h = jax.nn.silu(
            self._deq_ein("ecd,edf->ecf", expert_in, self.w_gate,
                          self.s_gate[:, None, :])
        ) * self._deq_ein("ecd,edf->ecf", expert_in, self.w_up,
                          self.s_up[:, None, :])
        return self._deq_ein("ecf,efd->ecd", h, self.w_down,
                             self.s_down[:, None, :])

    def _dense_ffn(self, x):
        h = jax.nn.silu(
            self._deq_ein("...d,edf->...ef", x, self.w_gate, self.s_gate)
        ) * self._deq_ein("...d,edf->...ef", x, self.w_up, self.s_up)
        return self._deq_ein("...ef,efd->...ed", h, self.w_down,
                             self.s_down)


def quantize_module(
    module: Module,
    *,
    filter_fn: Optional[Callable[[str, Module], bool]] = None,
) -> Module:
    """Replace every :class:`Linear` under ``module`` (in place) with a
    :class:`QuantizedLinear`, and every :class:`~torchdistx_tpu.nn.moe.MoE`
    with a :class:`QuantizedMoE` (stacked expert weights are where the
    bytes are on MoE models).  ``filter_fn(path, mod) -> bool`` limits
    which layers convert (e.g. keep an lm_head full-precision:
    ``lambda path, mod: "lm_head" not in path``).  Returns ``module``.
    """
    if isinstance(module, Linear):
        raise ValueError(
            "quantize_module replaces Linear CHILDREN; wrap a bare Linear "
            "with QuantizedLinear.from_linear(lin) instead"
        )
    if isinstance(module, MoE) and not isinstance(module, QuantizedMoE):
        # replacing the root in place is impossible; silently quantizing
        # only its router would skip >95% of the bytes
        raise ValueError(
            "quantize_module replaces MoE CHILDREN; convert a bare MoE "
            "with QuantizedMoE.from_moe(moe) instead"
        )
    replaced = []

    def walk(mod: Module, path: str) -> None:
        # recursive, no descent into replaced or filter-excluded layers:
        # a converted MoE already quantized its own router, and a layer
        # the filter rejected must not be partially quantized
        for name, child in list(mod._modules.items()):
            child_path = f"{path}.{name}" if path else name
            if isinstance(child, Linear):
                make = QuantizedLinear.from_linear
            elif isinstance(child, MoE) and not isinstance(
                child, QuantizedMoE
            ):
                make = QuantizedMoE.from_moe
            else:
                walk(child, child_path)
                continue
            if filter_fn is None or filter_fn(child_path, child):
                setattr(mod, name, make(child))
                replaced.append(child_path)

    walk(module, "")
    return module
