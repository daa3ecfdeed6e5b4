"""End-to-end example: deferred-init a model, quantize weights to int8,
and serve KV-cache generation — the weight-read-bound decode path at half
the HBM traffic of bf16 (quarter of f32).

Run on a TPU host:          python examples/quantized_inference.py
Run on CPU:                 JAX_PLATFORMS=cpu TDX_GEN_MODEL=tiny \
                            python examples/quantized_inference.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import torchdistx_tpu as tdx  # noqa: E402
from torchdistx_tpu.generation import generate  # noqa: E402
from torchdistx_tpu.models import Llama  # noqa: E402
from torchdistx_tpu.nn import QuantizedLinear, quantize_module  # noqa: E402


def param_gb(m):
    return sum(
        p.size * p.dtype.itemsize for _, p in m.named_parameters()
    ) / 1e9


def main():
    import jax

    name = os.environ.get("TDX_GEN_MODEL", "llama_1b")
    dtype = (
        jnp.bfloat16
        if jax.devices()[0].platform == "tpu"
        else jnp.float32
    )

    # 1. storage-less construction, then on-device materialization
    tdx.manual_seed(0)
    model = tdx.deferred_init(Llama.from_name, name, dtype=dtype)
    tdx.materialize_module(model)
    print(f"{name}: {model.num_params():,} params, {param_gb(model):.2f} GB")

    # 2. weight-only int8 — keep the lm_head full precision (last-layer
    # logits are the most quantization-sensitive spot)
    quantize_module(model, filter_fn=lambda path, mod: "lm_head" not in path)
    n_q = sum(
        isinstance(mod, QuantizedLinear) for _, mod in model.named_modules()
    )
    print(f"quantized {n_q} Linear layers -> {param_gb(model):.2f} GB")

    # 3. generate
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 256, (1, 32)), jnp.int32
    )
    out = generate(model, prompt, max_new_tokens=64)
    print("generated:", np.asarray(out)[0, -64:].tolist()[:16], "...")


if __name__ == "__main__":
    main()
