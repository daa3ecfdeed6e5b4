"""Measure BASELINE.json configs 1-3 and print one JSON line per config.

  1. deferred_init(Linear(1024, 1024)) -> materialize on CPU PJRT
  2. deferred_init(ResNet-50)          -> materialize on one TPU chip
  3. deferred_init(GPT-2-large)        -> materialize SHARDED across 8
     devices, with peak host RSS (the O(one-tensor) host-RAM claim)

Config 3 runs on the 8-virtual-device CPU mesh when 8 real chips are not
attached; the host-RSS discipline being measured is host-side either
way.  Run config 1+3 with:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python scripts/bench_baseline_configs.py --cpu

and config 2 with a TPU attached: python scripts/bench_baseline_configs.py
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def config1():
    import jax

    import torchdistx_tpu as tdx
    from torchdistx_tpu import nn

    t0 = time.time()
    m = tdx.deferred_init(lambda: nn.Linear(1024, 1024))
    tdx.materialize_module(m)
    jax.block_until_ready(m.weight)
    return {
        "config": 1,
        "what": "Linear(1024,1024) deferred+materialize, CPU PJRT",
        "wall_s": round(time.time() - t0, 3),
        "params": m.num_params(),
    }


def config2(replay_mode: str = "auto"):
    import jax

    import torchdistx_tpu as tdx
    from torchdistx_tpu._graph import RecordingSession
    from torchdistx_tpu.models.resnet import resnet50

    # "auto" resolves to chunked replay on TPU for the conv graph: its 34
    # distinct conv/BN closure shapes make op-by-op eager replay compile-
    # dominated (~160 per-op compiles), while the schedule chunks into 7
    # repeated jitted chunks.  --replay-mode
    # eager reproduces the old path for the A/B.
    RecordingSession.replay_mode = replay_mode
    t0 = time.time()
    tdx.manual_seed(0)
    m = tdx.deferred_init(resnet50)
    t_defer = time.time() - t0
    p0 = next(p for _, p in m.named_parameters())
    sess = p0._session
    t0 = time.time()
    tdx.materialize_module(m)
    jax.block_until_ready([p for _, p in m.named_parameters()])
    resolved = replay_mode
    if replay_mode == "auto":
        # self-describing A/B record: which executor actually ran
        resolved = "chunked" if sess.chunk_dispatches > 0 else "eager"
    return {
        "config": 2,
        "what": "ResNet-50 deferred+materialize, one TPU chip",
        "replay_mode_requested": replay_mode,
        "replay_mode_resolved": resolved,
        "chunk_compiles": sess.chunk_compiles,
        "chunk_dispatches": sess.chunk_dispatches,
        "deferred_s": round(t_defer, 3),
        "materialize_s": round(time.time() - t0, 3),
        "params": m.num_params(),
        "device": str(jax.devices()[0]),
    }


def config3():
    import jax

    import torchdistx_tpu as tdx
    from torchdistx_tpu.models import GPT2
    from torchdistx_tpu.parallel import create_mesh, fsdp_shard_rule

    mesh = create_mesh({"fsdp": 8})
    rss_before = _rss_gb()
    t0 = time.time()
    tdx.manual_seed(0)
    m = tdx.deferred_init(GPT2.from_name, "gpt2_large")
    t_defer = time.time() - t0
    t0 = time.time()
    tdx.materialize_module(m, sharding_rule=fsdp_shard_rule(mesh))
    jax.block_until_ready([p for _, p in m.named_parameters()])
    t_mat = time.time() - t0
    rss_after = _rss_gb()
    n = m.num_params()
    return {
        "config": 3,
        "what": "GPT-2-large deferred+materialize SHARDED over 8 devices",
        "deferred_s": round(t_defer, 3),
        "materialize_s": round(t_mat, 3),
        "params": n,
        "param_bytes_gb": round(n * 4 / 1e9, 3),
        "peak_host_rss_delta_gb": round(rss_after - rss_before, 3),
        "n_devices": len(jax.devices()),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true", help="configs 1+3 on CPU mesh")
    ap.add_argument(
        "--replay-mode",
        default="auto",
        choices=("auto", "eager", "chunked"),
        help="config-2 replay executor (auto -> chunked on TPU conv graphs)",
    )
    args = ap.parse_args()
    import jax

    from torchdistx_tpu.obs.ledger import record_stamp

    stamp = record_stamp()
    if args.cpu:
        print(json.dumps({**stamp, **config1()}))
        print(json.dumps({**stamp, **config3()}))
    else:
        print(json.dumps({**stamp, **config2(args.replay_mode)}))


if __name__ == "__main__":
    main()
