"""The main path's Pallas kernels COMPILE for the chip, at real widths.

Interpret mode (every other kernel test here) cannot show what Mosaic
refuses: a block whose last two dimensions do not tile, a kernel that
wants more fast memory than it may use.  The TPU's compiler is installed
beside the CPU backend and compiles for a chip that is described and not
attached, so each case lowers one kernel with ``interpret=False`` onto a
described ``v5e:2x2`` device from shapes alone and asks for the
executable.  Nothing runs; what is pinned is acceptance (and that the
program holds the Mosaic call), not results — those are the interpret
tests' and ``chip_smoke.py``'s.

Widths: head_dim 128; Hkv 32 (llama2_7b) and 8 (mistral_7b, llama3_8b);
slab 2048-4096; page 16; vocab 32000 and GPT-2's 50257.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU's library, pytest-xdist workers
all import this file, and only the worker that runs it may touch libtpu.
All cases live in this ONE file for the same reason (a second file could
land on another worker and skip in silence).  The persistent compilation
cache is off around these compiles: an entry written for a described
device cannot be read back without one, and warns on every later run.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from torchdistx_tpu.ops import decode_attention as da
from torchdistx_tpu.ops.flash_attention import flash_attention
from torchdistx_tpu.ops.fused_ce import fused_linear_cross_entropy


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs;
    returns the executable's HLO text."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _kernel_names(text):
    """The names the executable gives its Mosaic custom calls, without
    the instruction number: what a device trace shows each kernel as."""
    names = []
    for line in text.splitlines():
        head, sep, rest = line.strip().partition(" = ")
        if sep and 'custom_call_target="tpu_custom_call"' in rest:
            names.append(re.sub(r"[.\d]+$", "", head.split()[-1].lstrip("%")))
    return names


# The kernels' names are fixed by the program (``name=`` on each
# ``pl.pallas_call``) and the benchmark's trace readers find kernels by
# three rules on them, stated here and not imported, and checked on the
# names read from each executable:
#   1. flash forward: the name contains "flash_forward";
#   2. flash backward (dK/dV, dQ): contains neither "flash_forward" nor
#      "decode";
#   3. decode attention: does not contain "flash_forward".
FLASH_FORWARD = "tdx_flash_forward"
FLASH_BACKWARD = ["tdx_flash_backward_dkv", "tdx_flash_backward_dq"]
DECODE_NAMES = {
    "decode_attention": "tdx_decode_attention",
    "decode_attention_block": "tdx_decode_attention",
    "paged_decode_attention": "tdx_paged_decode_attention",
    "paged_decode_attention_block": "tdx_paged_decode_attention",
}


B, D, SLOTS_L = 8, 128, 2048  # decode batch (slots), head_dim, slab rows
PAGES, PS = 1024, 16  # pool pages, page size


def _decode_case(family, quantized, hq, hkv):
    """(fn, shapes) for one decode-attention family."""
    s = 4 if family.endswith("block") else 1  # verify block: K + 1 = 4
    kv = jnp.int8 if quantized else jnp.bfloat16
    paged = family.startswith("paged")
    rows = (PAGES, PS) if paged else (B, SLOTS_L)
    shapes = [
        ((B, s, hq, D), jnp.bfloat16),
        ((*rows, hkv, D), kv),
        ((*rows, hkv, D), kv),
    ]
    if paged:
        shapes.append(((B, SLOTS_L // PS), jnp.int32))
    shapes.append(((B,), jnp.int32))
    if quantized:
        shapes += [((*rows, hkv, 1), jnp.float32)] * 2
    kernel = getattr(da, family)

    def fn(q, ck, cv, *rest):
        scales = {}
        if quantized:
            *rest, ks, vs = rest
            scales = dict(k_scale=ks, v_scale=vs)
        return kernel(q, ck, cv, *rest, interpret=False, **scales)

    return fn, shapes


DECODE_CASES = [
    (family, quantized, 32, hkv)
    for family, quantized in [
        ("decode_attention", False),
        ("decode_attention_block", False),
        ("paged_decode_attention", False),
        ("paged_decode_attention_block", False),
        ("decode_attention", True),
        ("paged_decode_attention", True),
    ]
    for hkv in (32, 8)
]


@pytest.mark.parametrize(
    "family,quantized,hq,hkv", DECODE_CASES,
    ids=[
        f"{f}-{'int8' if q else 'bf16'}-hkv{hkv}"
        for f, q, _, hkv in DECODE_CASES
    ],
)
def test_decode_attention_compiles(one_chip, family, quantized, hq, hkv):
    fn, shapes = _decode_case(family, quantized, hq, hkv)
    text = _compile(fn, one_chip, *shapes)
    names = _kernel_names(text)
    assert names == [DECODE_NAMES[family]]
    assert "flash_forward" not in names[0] and "decode" in names[0]  # rule 3


@pytest.mark.parametrize(
    "b,s,hq,hkv,window,remat",
    [
        (2, 2048, 32, 32, None, False),  # llama2_7b MHA
        (1, 4096, 32, 8, None, False),  # mistral_7b / llama3_8b GQA
        (1, 4096, 32, 8, 1024, False),  # sliding window
        (4, 2048, 16, 16, None, True),  # dscoder-1.3b under full remat
    ],
    ids=["mha", "gqa", "gqa-window", "mha-remat"],
)
def test_flash_attention_fwd_bwd_compiles(
    one_chip, b, s, hq, hkv, window, remat
):
    def attend(q, k, v):
        return flash_attention(
            q, k, v, causal=True, window=window, interpret=False
        )

    def loss(q, k, v):
        out = (jax.checkpoint(attend) if remat else attend)(q, k, v)
        return jnp.sum(out.astype(jnp.float32))

    q = ((b, s, hq, D), jnp.bfloat16)
    kv = ((b, s, hkv, D), jnp.bfloat16)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv)
    # the same three names whatever wraps the call: under remat the
    # backward pair used to take the name "checkpoint", and the forward
    # kernel there is the recomputed one, which keeps the forward's name
    names = _kernel_names(text)
    assert sorted(names) == sorted([FLASH_FORWARD] + FLASH_BACKWARD)
    forward = [n for n in names if "flash_forward" in n]
    assert forward == [FLASH_FORWARD]  # rule 1
    assert not [n for n in names if n not in forward and "decode" in n]  # 2


@pytest.mark.parametrize(
    "n,d,v",
    [(4096, 2048, 32000), (2048, 1600, 50257)],
    ids=["llama_1b-v32000", "gpt2_xl-v50257"],
)
def test_fused_ce_fwd_bwd_compiles(one_chip, n, d, v):
    def loss(x, w, labels):
        return fused_linear_cross_entropy(x, w, labels, interpret=False)

    text = _compile(
        jax.grad(loss, argnums=(0, 1)), one_chip,
        ((n, d), jnp.bfloat16), ((v, d), jnp.bfloat16), ((n,), jnp.int32),
    )
    assert sorted(_kernel_names(text)) == [
        "tdx_fused_ce_backward_dw", "tdx_fused_ce_backward_dx",
        "tdx_fused_ce_forward",
    ]
