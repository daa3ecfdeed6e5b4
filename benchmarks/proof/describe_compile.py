"""Compile a cell's programs at real size for a *described* ``v5e:2x2``
(no chip time): what the chip's compiler would refuse, it refuses here,
and ``memory_analysis()`` says what one program needs of 15.75 GiB.

    JAX_PLATFORMS=cpu python benchmarks/proof/describe_compile.py <cell>

Nothing runs, so no result, time or rate comes out of this.  Two things
are steered from here and not through an option of the program: the
model's ``use_flash=None`` and the kernels' ``interpret=None`` both ask
``jax.devices()[0].platform``, so ``jax.devices`` is made to answer with
the described devices while the programs are lowered.  One process at a
time may load libtpu.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

GIB = 2**30
KERNEL = 'custom_call_target="tpu_custom_call"'


def report(what, compiled, t0):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{what}: compiled in {time.time() - t0:.1f} s, "
          f"{compiled.as_text().count(KERNEL)} Mosaic kernels, "
          f"arguments {m.argument_size_in_bytes / GIB:.3f} + temporaries "
          f"{m.temp_size_in_bytes / GIB:.3f} + outputs not aliased "
          f"{(m.output_size_in_bytes - m.alias_size_in_bytes) / GIB:.3f} "
          f"= {total / GIB:.3f} GiB of 15.75", flush=True)


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import torchdistx_tpu as tdx
    from harness import loader

    jax.config.update("jax_enable_compilation_cache", False)
    cell = loader.load_cell(argv[0])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[: cell.chips]
    mesh = Mesh(np.array(devices), ("fsdp",))
    whole = NamedSharding(mesh, P())
    family = loader.load_family(cell.config["family"])
    model = tdx.deferred_init(family.constructor(cell.config))
    real_devices = jax.devices
    described = lambda *a, **k: list(topo.devices)  # noqa: E731

    def shape(s, dtype, sharding=whole):
        return jax.ShapeDtypeStruct(tuple(s), dtype, sharding=sharding)

    if cell.driver_kind == "train_steps":
        from torchdistx_tpu.nn import functional
        from torchdistx_tpu.nn.module import functional_call
        from torchdistx_tpu.optimizers import anyprecision_adamw
        from torchdistx_tpu.parallel import ShardedTrainStep, fsdp_shard_rule

        rule = fsdp_shard_rule(mesh)
        params = {n: shape(p.shape, p.dtype, rule(n, p))
                  for n, p in model.named_parameters()}

        def loss_fn(p, b):
            tokens, labels = b
            return functional.cross_entropy(
                functional_call(model, p, (tokens,)), labels)

        opt = anyprecision_adamw(float(cell.traffic["optimizer"]["lr"]))
        step = ShardedTrainStep(loss_fn, opt, mesh, shard_axis="fsdp")
        from torchdistx_tpu.parallel.fsdp import optimizer_state_shardings

        state = jax.eval_shape(opt.init, params)
        shardings = optimizer_state_shardings(state, params, mesh)
        state = jax.tree_util.tree_map(
            lambda s, sh: shape(s.shape, s.dtype, sh), state, shardings)
        jax.devices = described
        try:
            step._build(params, state)
            rows = (int(cell.traffic["batch"]), int(cell.traffic["seq"]))
            batch_sh = NamedSharding(mesh, P("fsdp"))
            batch = tuple(shape(rows, jnp.int32, batch_sh) for _ in range(2))
            t0 = time.time()
            compiled = step._jitted.lower(
                params, state, batch, shape((), jnp.int32)).compile()
        finally:
            jax.devices = real_devices
        report(f"{cell.name} train step on {cell.chips} chip(s)", compiled, t0)
    elif cell.driver_kind == "serve_closed_loop":
        from torchdistx_tpu.serve import ServeEngine

        opts = dict(cell.traffic["engine"], cost_cards=False)
        opts["prefill_buckets"] = tuple(opts["prefill_buckets"])
        engine = ServeEngine(model, **opts)  # its cache lives on the CPU here
        params = {n: shape(p.shape, p.dtype) for n, p in model.named_parameters()}
        kv = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype), engine.cache.kv)
        n = engine.num_slots
        jax.devices = described
        try:
            for b in opts["prefill_buckets"]:
                t0 = time.time()
                compiled = engine._prefill_program(b).lower(
                    params, kv, shape((1, b), jnp.int32), shape((), jnp.int32),
                    shape((), jnp.int32), shape((1,), jnp.float32),
                    shape((1,), jnp.int32)).compile()
                report(f"{cell.name} prefill bucket {b}", compiled, t0)
            t0 = time.time()
            ints = [shape((n,), jnp.int32)] * 2
            compiled = engine._decode_program().lower(
                params, kv, *ints, shape((n,), jnp.float32), shape((n,), jnp.int32),
                shape((n,), jnp.int32), shape((n,), jnp.int32),
                shape((n,), jnp.bool_)).compile()
            report(f"{cell.name} decode program, {n} slots", compiled, t0)
        finally:
            jax.devices = real_devices
    else:
        print(f"no described compile is written for driver kind {cell.driver_kind}")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
