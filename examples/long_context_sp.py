"""End-to-end example: sequence-parallel long-context training.

A Llama model whose attention runs RING (flash kernel per block, K/V
rotating over ICI) or ULYSSES (two all-to-alls around local flash
attention) sequence parallelism: the sequence dimension is sharded over
an ``sp`` mesh axis, so the trainable context length scales with the
number of devices while per-device memory stays flat.

Run on a TPU host:          python examples/long_context_sp.py
Run on CPU (8 virtual):     XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                            JAX_PLATFORMS=cpu python examples/long_context_sp.py
Pick the strategy:          TDX_SP_MODE=ring|ulysses (default ring)

"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

import torchdistx_tpu as tdx


def main() -> None:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchdistx_tpu.models import Llama
    from torchdistx_tpu.nn import functional, functional_call
    from torchdistx_tpu.parallel import create_mesh

    sp_mode = os.environ.get("TDX_SP_MODE", "ring")
    mesh = create_mesh({"sp": -1})  # all local devices on the seq axis
    n = mesh.devices.size
    seq = int(os.environ.get("TDX_SEQ", "1024"))  # global context length

    # 1. deferred-init the SP model; params are replicated (the sp axis
    #    shards activations, not weights — compose sp x fsdp for both)
    tdx.manual_seed(0)
    model = tdx.deferred_init(
        Llama.from_name,
        "tiny",
        max_seq_len=seq,
        sp_axis="sp",
        sp_mode=sp_mode,
        n_heads=8,
        dim=128,
        dtype=jnp.float32,
    )
    tdx.materialize_module(
        model, sharding_rule=lambda path, fake: NamedSharding(mesh, P())
    )
    params = dict(model.named_parameters())
    print(
        f"{sp_mode} SP over {n} devices: global context {seq}, "
        f"{seq // n} per device"
    )

    # 2. the train step: tokens sharded over sp on the SEQUENCE dim; the
    #    model's attention communicates over the sp axis internally, so
    #    the whole step is one shard_map
    from jax import shard_map

    from torchdistx_tpu.parallel import collectives

    def loss_fn(p, tokens, labels):
        logits = functional_call(model, p, (tokens,))
        # through the audit choke point, not raw lax.pmean (TDX103)
        return collectives.all_mean(
            functional.cross_entropy(logits, labels), "sp"
        )

    tx = optax.adamw(3e-4)

    @jax.jit
    def train_step(p, opt_state, tokens, labels):
        def inner(p, tokens, labels):
            loss, grads = jax.value_and_grad(loss_fn)(p, tokens, labels)
            # grads of replicated params need no sync: every device saw
            # the same params and pmean'd loss -> identical grads
            return loss, grads

        loss, grads = shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(), P(None, "sp"), P(None, "sp")),
            out_specs=(P(), P()),
            check_vma=False,
        )(p, tokens, labels)
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, loss

    # 3. synthetic next-token data at the GLOBAL context length
    rs = np.random.RandomState(0)
    data = jnp.asarray(rs.randint(0, 256, (2, seq + 1)), jnp.int32)
    tokens, labels = data[:, :-1], data[:, 1:]

    opt_state = tx.init(params)
    for step in range(5):
        params, opt_state, loss = train_step(params, opt_state, tokens, labels)
        print(f"step {step}: loss {float(loss):.4f}")

    # 4. T5-style relative-position bias on the flash ring: bias rows
    #    shard with the queries (O(S) per device), key columns stay
    #    global; each hop streams its column slice into the kernels
    from torchdistx_tpu.ops.attention import ring_flash_attention

    h, d = 4, 32
    rsb = np.random.RandomState(1)
    qkv = jnp.asarray(rsb.randn(1, seq, h, d), jnp.float32)
    rel_bias = jnp.asarray(rsb.randn(h, seq, seq) * 0.5, jnp.float32)
    biased = shard_map(
        lambda q, k, v, b: ring_flash_attention(
            q, k, v, axis="sp", causal=True, bias=b
        ),
        mesh=mesh,
        in_specs=(
            P(None, "sp"), P(None, "sp"), P(None, "sp"),
            P(None, "sp", None),
        ),
        out_specs=P(None, "sp"),
        check_vma=False,
    )
    out = biased(qkv, qkv, qkv, rel_bias)
    print(
        f"biased flash-ring attention (T5 rel-pos) over {n} devices: "
        f"out {tuple(out.shape)}"
    )


if __name__ == "__main__":
    main()
