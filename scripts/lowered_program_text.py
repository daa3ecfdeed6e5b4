#!/usr/bin/env python
"""Digests of the lowered text of the programs that hold no expert layer:
Mistral-7B's serve decode and prefill programs and Jamba2-3B's (two and
fourteen layers at the published widths, 16 slots of 2048, lowered for a
described ``v5e:2x2`` chip from the signatures the engine dispatches),
and a small Llama train step (``ShardedTrainStep`` on the CPU's one
device).  A change that says it leaves those programs alone shows it by
the same digests on the parent and on itself; source paths are part of
the text, so both trees run FROM THE SAME PATH, one after the other:

    rm -rf /root/scratch/t && git archive PARENT | tar -x -C /root/scratch/t   (mkdir first)
    cp scripts/lowered_program_text.py /root/scratch/t/scripts/
    (cd /root/scratch/t && JAX_PLATFORMS=cpu python scripts/lowered_program_text.py)
    ... the same with ``git archive $(git write-tree)`` ...

Nothing runs and nothing is timed; one process at a time may load the
TPU's compiler.  PR 37 read the same seven digests on 9694199 and on its
own tree (``CHANGES.md``).
"""
import hashlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
TREE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, TREE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import torchdistx_tpu as tdx  # noqa: E402
from torchdistx_tpu.generation import SLOT_STATE_ROWS  # noqa: E402
from torchdistx_tpu.models import Jamba, Llama  # noqa: E402
from torchdistx_tpu.serve import ServeEngine  # noqa: E402

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one_chip = SingleDeviceSharding(topo.devices[0])
chip = topo.devices[0]
real_devices = jax.devices
out = {}

def sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16] + f" ({len(text)} chars)"

def serve_texts(tag, model, slots, max_len, buckets):
    engine = ServeEngine(model, num_slots=slots, max_len=max_len,
                         prefill_buckets=buckets, cost_cards=False)
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    ints = lambda *d: jax.ShapeDtypeStruct(d, jnp.int32, sharding=one_chip)
    params = {n: shape(p) for n, p in model.named_parameters()}
    kv = jax.tree_util.tree_map(shape, engine.cache.kv)
    jax.devices = lambda *a, **k: [chip]
    try:
        out[tag + ".decode"] = sha(engine._decode_program().lower(
            params, kv, ints(SLOT_STATE_ROWS, slots), ints(slots),
            ints(SLOT_STATE_ROWS + 1, slots)).as_text())
        for b in buckets:
            out[f"{tag}.prefill{b}"] = sha(engine._prefill_program(b).lower(
                params, kv, ints(slots), ints(1, b), ints(), ints(),
                jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one_chip),
                ints(1)).as_text())
    finally:
        jax.devices = real_devices

serve_texts("mistral", tdx.deferred_init(lambda: Llama.from_name(
    "mistral_7b", n_layers=2, vocab_size=32768, max_seq_len=2048,
    rope_theta=1e6, sliding_window=None, dtype=jnp.bfloat16)), 16, 2048, (128, 1024))
serve_texts("jamba", tdx.deferred_init(lambda: Jamba.from_name(
    "jamba2_3b", n_layers=14, max_seq_len=2048)), 16, 2048, (256, 1024))

# the train step: a small Llama, two layers, on the CPU's one device
from torchdistx_tpu.nn import functional  # noqa: E402
from torchdistx_tpu.nn.module import functional_call  # noqa: E402
from torchdistx_tpu.optimizers import anyprecision_adamw  # noqa: E402
from torchdistx_tpu.parallel import (  # noqa: E402
    ShardedTrainStep, create_mesh, fsdp_shard_rule,
)
mesh = create_mesh({"fsdp": 1}, devices=jax.devices()[:1])
tdx.manual_seed(0)
model = tdx.deferred_init(lambda: Llama.from_name(
    "tiny", dim=256, n_layers=2, n_heads=2, n_kv_heads=2, vocab_size=512,
    max_seq_len=256, dtype=jnp.bfloat16))
tdx.materialize_module(model, sharding_rule=fsdp_shard_rule(mesh))
params = dict(model.named_parameters())
def loss_fn(p, b):
    tokens, labels = b
    return functional.cross_entropy(functional_call(model, p, (tokens,)), labels)
step = ShardedTrainStep(loss_fn, anyprecision_adamw(1e-4), mesh, shard_axis="fsdp")
opt_state = step.init_optimizer(params)
batch = (jnp.zeros((2, 256), jnp.int32), jnp.zeros((2, 256), jnp.int32))
step._build(params, opt_state)
out["train.step"] = sha(step._jitted.lower(params, opt_state, batch, jnp.int32(0)).as_text())
print(json.dumps(out, indent=1))
