"""The north-star demo (BASELINE.json config 5): construct Llama-2-7B with
zero array storage, inspect it, then materialize onto the accelerator —
sharded across every available device — in seconds with flat host RAM.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import resource
import time

import jax

import torchdistx_tpu as tdx
from torchdistx_tpu.models import Llama
from torchdistx_tpu.parallel import create_mesh, fsdp_shard_rule


def rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def main() -> None:
    t0 = time.time()
    tdx.manual_seed(0)
    model = tdx.deferred_init(Llama.from_name, "llama2_7b")
    print(
        f"deferred_init: {time.time()-t0:.1f}s | "
        f"{model.num_params()/1e9:.2f}B params | host RSS {rss_gb():.2f} GB"
    )
    print("first weight:", repr(model.tok_emb.weight))

    n = len(jax.devices())
    t0 = time.time()
    if n > 1:
        mesh = create_mesh({"fsdp": n})
        tdx.materialize_module(model, sharding_rule=fsdp_shard_rule(mesh))
    else:
        tdx.materialize_module(model)
    jax.block_until_ready(model.norm.weight)
    print(
        f"materialize onto {n} device(s): {time.time()-t0:.1f}s | "
        f"host RSS {rss_gb():.2f} GB"
    )
    print("first weight now:", type(model.tok_emb.weight).__name__,
          model.tok_emb.weight.sharding)


if __name__ == "__main__":
    main()
