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

Widths: head_dim 128; Hkv 32 (llama2_7b), 8 (mistral_7b, llama3_8b) and
16 of 16 heads (deepseek-coder-1.3b); slab 2048-4096; page 16; vocab 32000 and GPT-2's 50257; the DeepSeek-V3
family at kanana-2-30b-a3b's: a 576-lane latent row (stored on 640) in 32
slots of 8192, 32 heads at qk 192 / v 128, 128 experts of 2048 x 768.

The compiled program is also where a LAYOUT shows: the serve cache is
stored as the decode kernel's operand (``serve/kv_cache.py``), and
``test_decode_step_leaves_the_cache_in_place`` pins that a decode
step's write + attend holds no instruction that relayouts or copies an
array of the cache's size (on the chip a ``(…, Hkv, D)`` array and its
``(…, Hkv * D)`` "view" are tiled differently, and the reshape between
them was 29 % of a Mistral-7B decode step).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU's library, pytest-xdist workers
all import this file, and only the worker that runs it may touch libtpu.
All cases live in this ONE file for the same reason (a second file could
land on another worker and skip in silence).  The persistent compilation
cache is off around these compiles: an entry written for a described
device cannot be read back without one, and warns on every later run.
"""

import base64
import math
import os
import re
import struct

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from torchdistx_tpu.ops import decode_attention as da
from torchdistx_tpu.ops.attention import slot_cached_attention
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


def _compile(fn, one_chip, *shapes, donate=()):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs;
    returns the executable's HLO text."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    text = (
        jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()
    )
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


def _has_grid(text, grid):
    """Every Mosaic kernel of the executable iterates over ``grid``.  A
    kernel travels in the custom call's ``backend_config`` as MLIR
    bytecode, base64; its ``iteration_bounds`` is a dense i64 array
    attribute, which the bytecode holds as its little-endian words."""
    bodies = [
        base64.b64decode(b) for b in re.findall(r'"body":"([^"]+)"', text)
    ]
    assert bodies, "no serialized Mosaic kernel in the program"
    want = struct.pack(f"<{len(grid)}q", *grid)
    return all(want in body for body in bodies)


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
    shapes = [  # the caches as the engine stores them: head tail merged
        ((B, s, hq, D), jnp.bfloat16),
        ((*rows, hkv * D), kv),
        ((*rows, hkv * D), kv),
    ]
    if paged:
        shapes.append(((B, SLOTS_L // PS), jnp.int32))
    shapes.append(((B,), jnp.int32))
    if quantized:
        shapes += [((*rows, hkv), jnp.float32)] * 2
    kernel = getattr(da, family)

    def fn(q, ck, cv, *rest):
        scales = {}
        if quantized:
            *rest, ks, vs = rest
            scales = dict(k_scale=ks, v_scale=vs)
        return kernel(q, ck, cv, *rest, interpret=False, **scales)

    return fn, shapes


DECODE_CASES = [
    (family, quantized, hq, hkv)
    for family, quantized in [
        ("decode_attention", False),
        ("decode_attention_block", False),
        ("paged_decode_attention", False),
        ("paged_decode_attention_block", False),
        ("decode_attention", True),
        ("paged_decode_attention", True),
    ]
    for hq, hkv in ((32, 32), (32, 8), (16, 16))
]


@pytest.mark.parametrize(
    "family,quantized,hq,hkv", DECODE_CASES,
    ids=[
        f"{f}-{'int8' if q else 'bf16'}-hkv{hkv}" + ("" if hq == 32 else f"of{hq}")
        for f, q, hq, hkv in DECODE_CASES
    ],
)
def test_decode_attention_compiles(one_chip, family, quantized, hq, hkv):
    fn, shapes = _decode_case(family, quantized, hq, hkv)
    text = _compile(fn, one_chip, *shapes)
    names = _kernel_names(text)
    assert names == [DECODE_NAMES[family]]
    assert "flash_forward" not in names[0] and "decode" in names[0]  # rule 3
    # one grid step reads its rows for every KV head (PR 33)
    s = 4 if family.endswith("block") else 1
    g, block_k = da._blocking(
        hkv, D, 1 if quantized else 2, SLOTS_L, -(-s * (hq // hkv) // 8) * 8,
        *((PS, PS) if family.startswith("paged") else (512,)),
    )
    assert g == hkv
    assert _has_grid(text, (B, 1, SLOTS_L // block_k))


# One layer's decode write + attend at Mistral-7B widths (the serve
# cell's: 16 slots of 2048 rows, 32 / 8 heads of 128), through
# ``slot_cached_attention`` with the cache donated, as the engine's decode
# program runs it 24 times a step.
M_B, M_L, M_HQ, M_HKV = 16, 2048, 32, 8
# what may have a result of the cache's size: the cache coming in and
# going out, and the in-place write of the new rows
IN_PLACE = {"dynamic-update-slice", "scatter"}
PLUMBING = {"parameter", "tuple", "get-tuple-element", "bitcast", "while"}
_INSTRUCTION = re.compile(
    r"\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<type>\(.*?\)|\S+) "
    r"(?P<op>[\w\-]+)\("
)
_ARRAY = re.compile(r"\w+\[([\d,]*)\](\{[^}]*\})?")


def _cache_sized(text, n):
    """``(computation, name, opcode, result type, line)`` of every
    instruction of the module, fused computations' bodies included,
    with an array of ``n`` elements in its result."""
    found, computation = [], None
    for line in text.splitlines():
        if line and not line[0].isspace():
            head = line.split()
            computation = head[1 if head[0] == "ENTRY" else 0].lstrip("%")
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        sizes = [
            math.prod(int(x) for x in dims.split(",") if x)
            for dims, _ in _ARRAY.findall(m["type"])
        ]
        if n in sizes:
            found.append((computation, m["name"], m["op"], m["type"], line))
    return found


def _same_tiling(type_):
    """True when every array of a (copy-start's) result type has one
    shape and one tiling: a move between memory spaces (``S(n)``), which
    the compiler's memory-space assignment may schedule for a kernel's
    operand, and not a relayout."""
    seen = {
        (dims, re.sub(r"S\(\d+\)", "", layout))
        for dims, layout in _ARRAY.findall(type_)
        if dims.count(",")  # not the u32[] context word
    }
    return len(seen) == 1


def _relayouts_of_the_cache(text, n):
    """Instructions with a result of the cache's size (``n`` elements)
    that are neither plumbing, nor the in-place write of the new rows
    (and the fusion around it), nor a move between memory spaces."""
    found = _cache_sized(text, n)
    updated = {c for c, _, op, _, _ in found if op in IN_PLACE}
    assert updated, "the write of the new rows is not in the program"
    offenders = []
    for _, name, op, type_, line in found:
        if op in IN_PLACE or op in PLUMBING:
            continue
        if op == "fusion":  # only the fusion around an in-place write
            called = re.search(r"calls=%?([\w.\-]+)", line)
            if called and called[1] in updated:
                continue
        if op in ("copy-start", "copy-done") and _same_tiling(type_):
            continue
        offenders.append(f"{name} = {type_} {op}")
    return offenders


@pytest.mark.parametrize(
    "paged,quantized,steps",
    [(False, False, 8), (False, True, 4), (True, False, M_L // PS)],
    ids=["slab-bf16", "slab-int8", "paged16-bf16"],
)
def test_decode_step_leaves_the_cache_in_place(
    one_chip, monkeypatch, paged, quantized, steps
):
    """The cache reaches the kernel as it is stored: besides parameters
    and tuple plumbing, only the in-place row write (and the fusion
    around it) has a result of the cache's size — no ``reshape``,
    ``copy``, ``transpose`` or other fusion.  On the ``(…, Hkv, D)``
    storage this failed with two ``reshape`` instructions a layer.
    The kernel's grid is ``steps`` row blocks a slot for all eight KV
    heads at once: 128 grid steps a call on the bf16 slab, where a grid
    step a head and 512 rows a block made 512 (PERF.md §6 PR 33)."""
    (chip,) = one_chip.device_set
    # ``interpret=None`` and ``use_flash`` ask jax.devices()[0].platform
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [chip])
    lead = (M_B * M_L // PS + 1, PS) if paged else (M_B, M_L)
    kv = jnp.int8 if quantized else jnp.bfloat16
    row = ((M_B, 1, M_HKV, D), jnp.bfloat16)
    cache = [((*lead, M_HKV * D), kv)] * 2
    if quantized:
        cache += [((*lead, M_HKV), jnp.float32)] * 2
    ints = [((M_B,), jnp.int32)]
    if paged:
        ints.append(((M_B, M_L // PS), jnp.int32))

    def fn(q, k_new, v_new, positions, *rest):
        *tables, cache = rest
        return slot_cached_attention(
            q, k_new, v_new, cache, positions, use_flash=True,
            page_tables=tables[0] if tables else None,
        )

    shapes = [((M_B, 1, M_HQ, D), jnp.bfloat16), row, row, *ints]
    n = len(shapes)
    text = _compile(
        lambda *a: fn(*a[:n], tuple(a[n:])), one_chip, *shapes, *cache,
        donate=tuple(range(n, n + len(cache))),
    )
    assert _kernel_names(text) == [
        "tdx_paged_decode_attention" if paged else "tdx_decode_attention"
    ]
    assert _has_grid(text, (M_B, 1, steps))
    offenders = _relayouts_of_the_cache(text, math.prod(lead) * M_HKV * D)
    assert not offenders, offenders


def test_serve_decode_program_compiles_from_the_packed_state(
    one_chip, monkeypatch
):
    """The engine's whole decode program at Mistral-7B's widths (two
    layers of the cell's 24; 16 slots of 2048), lowered from the
    signature the engine dispatches — ``(params, kv, carry, firsts,
    state)``: the per-slot state ONE host argument
    (``generation.pack_slot_state``, PR 31) with the row that says which
    slots start from it, the rest from the last dispatch's carry, which
    like the prefills' first tokens stays on the device (PR 35) — and
    compiled for the described chip: the unpacking and the choice cost
    the program no kernel (one decode-attention call a layer, as before)
    and nothing of the cache's size is copied.
    ``benchmarks/proof/describe_compile.py`` spells the seven-argument
    list of before PR 31; this is the described compile of the new one."""
    import torchdistx_tpu as tdx
    from torchdistx_tpu.generation import SLOT_STATE_ROWS
    from torchdistx_tpu.models import Llama
    from torchdistx_tpu.serve import ServeEngine

    (chip,) = one_chip.device_set
    layers = 2
    model = tdx.deferred_init(
        lambda: Llama.from_name(
            "mistral_7b", n_layers=layers, vocab_size=32768,
            max_seq_len=M_L, rope_theta=1e6, sliding_window=None,
            dtype=jnp.bfloat16,
        )
    )
    engine = ServeEngine(  # its (small) cache lives on the CPU
        model, num_slots=M_B, max_len=M_L, prefill_buckets=(128,),
        cost_cards=False,
    )

    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = {n: shape(p) for n, p in model.named_parameters()}
    kv = jax.tree_util.tree_map(shape, engine.cache.kv)
    def ints(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=one_chip)

    small = (
        ints(SLOT_STATE_ROWS, M_B), ints(M_B), ints(SLOT_STATE_ROWS + 1, M_B)
    )
    # ``interpret=None`` and ``use_flash`` ask jax.devices()[0].platform
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [chip])
    text = (
        engine._decode_program().lower(params, kv, *small).compile().as_text()
    )
    assert _kernel_names(text) == ["tdx_decode_attention"] * layers
    assert _has_grid(text, (M_B, 1, 8))  # 128 grid steps a layer
    offenders = _relayouts_of_the_cache(text, M_B * M_L * M_HKV * D)
    assert not offenders, offenders


# The DeepSeek-V3 family at kanana-2-30b-a3b's widths (the serve cell's:
# 32 slots of 8192 rows; 32 heads; a latent row of 512 + 64 lanes stored
# on 640; 128 experts of 2048 x 768, top 6).
K_B, K_L, K_H, K_W, K_R = 32, 8192, 32, 640, 512
_DYNAMIC = -(2**63)  # a grid bound known only at run time
K_E, K_D, K_F, K_TOP = 128, 2048, 768, 6


def test_latent_decode_step_leaves_the_cache_in_place(one_chip, monkeypatch):
    """One layer's latent decode write + attend, the cache donated, as
    the engine's decode program runs it: ``tdx_latent_decode_attention``
    is handed the slab as it is stored, so each visible row is the
    operand of that one call, its grid is the traced count of visible
    row blocks, and nothing of the cache's size is copied or
    relayouted.  (Stored 576 lanes wide the slab failed this: 576 is
    no multiple of 128, the compiler stored it rows-minor and relayouted
    1.3 GB a layer and step.)"""
    from torchdistx_tpu.ops.attention import latent_slot_cached_attention

    (chip,) = one_chip.device_set
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [chip])

    def fn(q, row, positions, latent):
        return latent_slot_cached_attention(
            q, row, (latent,), positions, value_width=K_R,
            scale=192**-0.5, use_flash=True,
        )

    text = _compile(
        fn, one_chip, ((K_B, K_H, K_W), jnp.bfloat16),
        ((K_B, 1, K_W), jnp.bfloat16), ((K_B,), jnp.int32),
        ((K_B, K_L, K_W), jnp.bfloat16), donate=(3,),
    )
    assert _kernel_names(text) == ["tdx_latent_decode_attention"]
    # one grid axis whose bound is traced (MLIR's dynamic size): a step
    # per visible (slot, row block), where a fixed (32, 16) grid of
    # 512-row blocks ran two thirds of its steps on rows no slot sees
    assert _has_grid(text, (_DYNAMIC,))
    assert not _has_grid(text, (K_B, K_L // 512))
    offenders = _relayouts_of_the_cache(text, K_B * K_L * K_W)
    assert not offenders, offenders


@pytest.mark.parametrize("tokens", [32, 4096], ids=["decode32", "prefill4096"])
def test_grouped_matmul_compiles(one_chip, tokens):
    """The experts' SwiGLU as two grouped matmuls (gate and up fused,
    then down) over a decode step's 192 rows (16-row tiles) and a
    prefill's 24576 (128-row tiles), the layout built by counting."""
    from torchdistx_tpu.ops.grouped_matmul import (
        grouped_matmul, plan_groups, row_tile,
    )

    def experts(x, ids, w_gate, w_up, w_down):
        plan = plan_groups(ids, K_E, row_tile(ids.shape[0], K_E, x.dtype))
        kw = dict(use_kernel=True, interpret=False)
        h = grouped_matmul(
            x[plan.src // K_TOP], w_gate, plan, rhs_up=w_up, block_n=384, **kw
        )
        return grouped_matmul(h, w_down, plan, block_n=512, **kw)[plan.dest]

    text = _compile(
        experts, one_chip, ((tokens, K_D), jnp.bfloat16),
        ((tokens * K_TOP,), jnp.int32), ((K_E, K_D, K_F), jnp.bfloat16),
        ((K_E, K_D, K_F), jnp.bfloat16), ((K_E, K_F, K_D), jnp.bfloat16),
    )
    assert _kernel_names(text) == ["tdx_grouped_matmul"] * 2


def test_flash_forward_at_unequal_widths_compiles(one_chip):
    """Multi-head latent attention's prefill: scores on 192 lanes,
    128-wide values, one ``tdx_flash_forward`` (forward only)."""
    text = _compile(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, scale=192**-0.5, interpret=False
        ),
        one_chip, ((1, 4096, K_H, 192), jnp.bfloat16),
        ((1, 4096, K_H, 192), jnp.bfloat16),
        ((1, 4096, K_H, 128), jnp.bfloat16),
    )
    assert _kernel_names(text) == [FLASH_FORWARD]


@pytest.mark.parametrize(
    "b,s,hq,hkv,d,dtype,window,remat",
    [
        (2, 2048, 32, 32, D, jnp.bfloat16, None, False),  # llama2_7b MHA
        (1, 4096, 32, 8, D, jnp.bfloat16, None, False),  # mistral_7b GQA
        (1, 4096, 32, 8, D, jnp.bfloat16, 1024, False),  # sliding window
        # dscoder-1.3b under full remat: the train cell's three kernels
        (4, 2048, 16, 16, D, jnp.bfloat16, None, True),
        # the kernels' products take the rows' dtype (bf16 above, where
        # P^T dO and dS^T Q contract bf16 tiles over their FIRST
        # dimension); float32 rows keep float32 products
        (4, 2048, 16, 16, D, jnp.float32, None, False),
        (1, 3072, 16, 2, 256, jnp.bfloat16, None, False),  # Qwen3-Next's
    ],
    ids=["mha", "gqa", "gqa-window", "mha-remat", "mha-f32", "gqa-head256"],
)
def test_flash_attention_fwd_bwd_compiles(
    one_chip, b, s, hq, hkv, d, dtype, window, remat
):
    def attend(q, k, v):
        return flash_attention(
            q, k, v, causal=True, window=window, interpret=False
        )

    def loss(q, k, v):
        out = (jax.checkpoint(attend) if remat else attend)(q, k, v)
        return jnp.sum(out.astype(jnp.float32))

    q = ((b, s, hq, d), dtype)
    kv = ((b, s, hkv, d), dtype)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv)
    # the same three names whatever wraps the call: under remat the
    # backward pair used to take the name "checkpoint", and the forward
    # kernel there is the recomputed one, which keeps the forward's name
    names = _kernel_names(text)
    assert sorted(names) == sorted([FLASH_FORWARD] + FLASH_BACKWARD)
    forward = [n for n in names if "flash_forward" in n]
    assert forward == [FLASH_FORWARD]  # rule 1
    assert not [n for n in names if n not in forward and "decode" in n]  # 2


def test_flash_biased_backward_compiles(one_chip):
    """T5's materialised bias (12 heads of 64, bf16 rows): the recompute
    that every backward kernel shares takes bf16 products there too.
    (The bucket-TABLE mode is not here: Mosaic refuses its (1, buckets)
    block of an (H, buckets) table, at the parent commit as well.)"""
    b, s, h, d = 2, 1024, 12, 64

    def loss(q, k, v, bias):
        out = flash_attention(
            q, k, v, bias=bias, causal=False, interpret=False
        )
        return jnp.sum(out.astype(jnp.float32))

    qkv = ((b, s, h, d), jnp.bfloat16)
    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3)), one_chip, qkv, qkv, qkv,
        ((h, s, s), jnp.bfloat16),
    )
    assert sorted(_kernel_names(text)) == sorted(
        [FLASH_FORWARD] + FLASH_BACKWARD + ["tdx_flash_backward_dbias"]
    )


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


# -- AI21-Jamba2-3B: the selective-scan kernels and MQA at 20 / 1 ------------

J_C, J_N, J_SLOTS, J_L = 5120, 16, 256, 2048  # d_inner, d_state, slots, rows


@pytest.mark.parametrize("bucket", [256, 512, 1024])
def test_selective_scan_compiles(one_chip, bucket):
    """``tdx_selective_scan`` at the published widths over each bucket
    of the Jamba cell: the (16, 1024) state block resident across the
    time chunks, ``B`` and ``C`` as (rows, 16, 1) columns."""
    from torchdistx_tpu.ops.selective_scan import selective_scan

    def fn(x, dt, a, b, c, dskip, z, h0, true_len):
        return selective_scan(
            x, dt, a, b, c, dskip, z, h0, true_len[0],
            use_kernel=True, interpret=False,
        )

    rows = ((1, bucket, J_C), jnp.bfloat16)
    coef = ((1, bucket, J_N), jnp.float32)
    text = _compile(
        fn, one_chip, rows, ((1, bucket, J_C), jnp.float32),
        ((J_N, J_C), jnp.float32), coef, coef, ((J_C,), jnp.float32), rows,
        ((1, J_N, J_C), jnp.float32), ((1,), jnp.int32),
    )
    assert _kernel_names(text) == ["tdx_selective_scan"]
    assert _has_grid(text, (1, J_C // 1024, bucket // 128))


def test_state_update_compiles_and_leaves_the_state_in_place(one_chip):
    """``tdx_selective_state_update`` over 256 slots: the donated state
    is the kernel's operand AND its result (``input_output_aliases``),
    so nothing else in the program has a result of the state's size
    (84 MB a layer: a copy would double a decode step's second memory
    stream)."""
    from torchdistx_tpu.ops.selective_scan import selective_state_update

    def fn(h, x, dt, a, b, c, dskip, z):
        return selective_state_update(
            h, x, dt, a, b, c, dskip, z, use_kernel=True, interpret=False
        )

    rows = ((J_SLOTS, J_C), jnp.bfloat16)
    coef = ((J_SLOTS, J_N), jnp.float32)
    text = _compile(
        fn, one_chip, ((J_SLOTS, J_N, J_C), jnp.float32), rows,
        ((J_SLOTS, J_C), jnp.float32), ((J_N, J_C), jnp.float32), coef, coef,
        ((J_C,), jnp.float32), rows, donate=(0,),
    )
    assert _kernel_names(text) == ["tdx_selective_state_update"]
    assert _has_grid(text, (J_SLOTS // 16, J_C // 1280))
    others = [
        f"{name} = {type_} {op}"
        for _, name, op, type_, _ in _cache_sized(text, J_SLOTS * J_N * J_C)
        if op not in PLUMBING and op != "custom-call"
    ]
    assert not others, others
    assert "output_to_operand_aliasing" in text or "input_output_alias" in text


def test_mqa_decode_step_leaves_the_cache_in_place(one_chip, monkeypatch):
    """The Jamba cell's two attention layers: 20 query heads on ONE KV
    head of 128 (a group of 20, a 256-byte row an array), 256 slots of
    2048 — ``_blocking``'s rule at a shape no other cell has."""
    (chip,) = one_chip.device_set
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [chip])
    row = ((J_SLOTS, 1, 1, D), jnp.bfloat16)
    cache = ((J_SLOTS, J_L, D), jnp.bfloat16)

    def fn(q, k_new, v_new, positions, ck, cv):
        return slot_cached_attention(
            q, k_new, v_new, (ck, cv), positions, use_flash=True
        )

    text = _compile(
        fn, one_chip, ((J_SLOTS, 1, 20, D), jnp.bfloat16), row, row,
        ((J_SLOTS,), jnp.int32), cache, cache, donate=(4, 5),
    )
    assert _kernel_names(text) == ["tdx_decode_attention"]
    offenders = _relayouts_of_the_cache(text, J_SLOTS * J_L * D)
    assert not offenders, offenders


def test_mqa_flash_prefill_compiles(one_chip):
    """A Jamba prefill's attention: 20 query heads on one KV head."""
    text = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        one_chip, ((1, 1024, 20, D), jnp.bfloat16),
        ((1, 1024, 1, D), jnp.bfloat16), ((1, 1024, 1, D), jnp.bfloat16),
    )
    assert _kernel_names(text) == [FLASH_FORWARD]


# The Qwen3-Next family at Qwen3-Next-80B-A3B's widths (the serve cell's:
# 128 slots of 4096; Gated DeltaNet 16 key / 32 value heads of 128, a
# (128, 128) float32 state a head; attention 16 query on 2 KV heads of
# 256; 128 of 512 experts of 2048 x 512 held, top 10).
Q_SLOTS, Q_L, Q_HK, Q_HV, Q_D = 128, 4096, 16, 32, 128
Q_HQ, Q_HKV, Q_HEAD = 16, 2, 256
Q_HELD, Q_WIDTH, Q_F, Q_TOP = 128, 512, 512, 10


@pytest.mark.parametrize("bucket", [512, 3072])
def test_gated_delta_chunk_compiles(one_chip, bucket):
    """``tdx_gated_delta_chunk`` at the published widths over the cell's
    smallest and largest bucket: a head's (128, 128) state resident
    across its chunks of 128 rows, the products in float32."""
    from torchdistx_tpu.ops.gated_delta import gated_delta_chunk

    def fn(q, k, v, g, beta, s0, true_len):
        return gated_delta_chunk(
            q, k, v, g, beta, s0, true_len[0], use_kernel=True, interpret=False
        )

    keys = ((1, bucket, Q_HK, Q_D), jnp.float32)
    gates = ((1, bucket, Q_HV), jnp.float32)
    text = _compile(
        fn, one_chip, keys, keys, ((1, bucket, Q_HV, Q_D), jnp.bfloat16),
        gates, gates, ((1, Q_HV, Q_D, Q_D), jnp.float32), ((1,), jnp.int32),
    )
    assert _kernel_names(text) == ["tdx_gated_delta_chunk"]
    assert _has_grid(text, (1, Q_HV, bucket // 128))


def test_gated_delta_update_compiles_and_leaves_the_state_in_place(one_chip):
    """``tdx_gated_delta_update`` over 128 slots: the donated state is
    the kernel's operand AND its result (``input_output_aliases``), so
    nothing else in the program has a result of the state's size (268 MB
    a layer: a copy would double a decode step's second memory stream)."""
    from torchdistx_tpu.ops.gated_delta import gated_delta_update

    def fn(s, q, k, v, g, beta):
        return gated_delta_update(
            s, q, k, v, g, beta, use_kernel=True, interpret=False
        )

    keys = ((Q_SLOTS, Q_HK, Q_D), jnp.float32)
    gates = ((Q_SLOTS, Q_HV), jnp.float32)
    text = _compile(
        fn, one_chip, ((Q_SLOTS, Q_HV, Q_D, Q_D), jnp.float32), keys, keys,
        ((Q_SLOTS, Q_HV, Q_D), jnp.bfloat16), gates, gates, donate=(0,),
    )
    assert _kernel_names(text) == ["tdx_gated_delta_update"]
    assert _has_grid(text, (Q_SLOTS, Q_HV // 16))
    others = [
        f"{name} = {type_} {op}"
        for _, name, op, type_, _ in _cache_sized(
            text, Q_SLOTS * Q_HV * Q_D * Q_D)
        if op not in PLUMBING and op != "custom-call"
    ]
    assert not others, others
    assert "output_to_operand_aliasing" in text or "input_output_alias" in text


def test_head_256_decode_step_leaves_the_cache_in_place(one_chip, monkeypatch):
    """The Qwen3-Next cell's two attention layers: 16 query heads on 2 KV
    heads of 256 (a group of 8, a 1024-byte row an array), 128 slots of
    4096 — ``_blocking``'s rule at a head no other cell has."""
    (chip,) = one_chip.device_set
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [chip])
    row = ((Q_SLOTS, 1, Q_HKV, Q_HEAD), jnp.bfloat16)
    cache = ((Q_SLOTS, Q_L, Q_HKV * Q_HEAD), jnp.bfloat16)

    def fn(q, k_new, v_new, positions, ck, cv):
        return slot_cached_attention(
            q, k_new, v_new, (ck, cv), positions, use_flash=True
        )

    text = _compile(
        fn, one_chip, ((Q_SLOTS, 1, Q_HQ, Q_HEAD), jnp.bfloat16), row, row,
        ((Q_SLOTS,), jnp.int32), cache, cache, donate=(4, 5),
    )
    assert _kernel_names(text) == ["tdx_decode_attention"]
    offenders = _relayouts_of_the_cache(text, Q_SLOTS * Q_L * Q_HKV * Q_HEAD)
    assert not offenders, offenders


def test_head_256_flash_prefill_compiles(one_chip):
    """A Qwen3-Next prefill's attention: 16 query heads on 2 KV heads of
    256 over the largest bucket."""
    text = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        one_chip, ((1, 3072, Q_HQ, Q_HEAD), jnp.bfloat16),
        ((1, 3072, Q_HKV, Q_HEAD), jnp.bfloat16),
        ((1, 3072, Q_HKV, Q_HEAD), jnp.bfloat16),
    )
    assert _kernel_names(text) == [FLASH_FORWARD]


@pytest.mark.parametrize("tokens", [128, 3072], ids=["decode128", "prefill3072"])
def test_grouped_matmul_over_a_share_compiles(one_chip, tokens):
    """The held experts' SwiGLU at width 512 (one column block of 512
    for gate and up) over the layout of a share: sized by the rows an
    even router sends here, twice over (a decode step's 1,280 choices:
    640 rows in 16-row tiles; a prefill's 30,720: 15,360 in 64-row
    tiles), the rows held elsewhere in no tile."""
    from torchdistx_tpu.ops.grouped_matmul import (
        grouped_matmul, plan_groups, row_tile,
    )

    expected = tokens * Q_TOP * Q_HELD // Q_WIDTH
    tm = row_tile(expected, Q_HELD, jnp.bfloat16)
    tiles = -(-2 * expected // tm) + Q_HELD

    def experts(x, ids, w_gate, w_up, w_down):
        plan = plan_groups(ids, Q_HELD, tm, tiles)  # an id >= 128: elsewhere
        kw = dict(use_kernel=True, interpret=False)
        h = grouped_matmul(
            x[plan.src // Q_TOP], w_gate, plan, rhs_up=w_up, block_n=512, **kw
        )
        y = grouped_matmul(h, w_down, plan, block_n=512, **kw)
        # zeros for a row held elsewhere
        return y.at[plan.dest].get(mode="fill", fill_value=0)

    text = _compile(
        experts, one_chip, ((tokens, 2048), jnp.bfloat16),
        ((tokens * Q_TOP,), jnp.int32), ((Q_HELD, 2048, Q_F), jnp.bfloat16),
        ((Q_HELD, 2048, Q_F), jnp.bfloat16), ((Q_HELD, Q_F, 2048), jnp.bfloat16),
    )
    assert _kernel_names(text) == ["tdx_grouped_matmul"] * 2


def test_share_layer_compiles_with_its_fallback(one_chip, monkeypatch):
    """One expert layer of the Qwen3-Next cell (128 of 512 experts held,
    no shared expert) at a prefill's 2,048 tokens: the layout sized by
    the rows held and the full-size one it falls back to are the two
    branches of a ``conditional``, each with its two kernels, and both
    lower for the chip."""
    import torchdistx_tpu as tdx
    from torchdistx_tpu.nn import functional_call
    from torchdistx_tpu.nn.moe import MoE, moe_count_tape, tape_totals

    (chip,) = one_chip.device_set
    layer = tdx.deferred_init(
        lambda: MoE(2048, Q_F, Q_WIDTH, top_k=Q_TOP, dtype=jnp.bfloat16,
                    dispatch_mode="grouped", held=(0, Q_HELD), use_kernel=True)
    )
    params = {
        n: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip)
        for n, p in layer.named_parameters()
    }

    def fn(params, x):
        with moe_count_tape() as tape:
            y = functional_call(layer, params, (x,))
        return y, tape_totals(tape)

    # ``interpret=None`` asks jax.devices()[0].platform
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [chip])
    text = jax.jit(fn).lower(
        params,
        jax.ShapeDtypeStruct((1, 2048, 2048), jnp.bfloat16, sharding=one_chip),
    ).compile().as_text()
    assert _kernel_names(text) == ["tdx_grouped_matmul"] * 4
    assert " conditional(" in text


def test_qwen3_next_serve_programs_compile(one_chip, monkeypatch):
    """The engine's decode program and its largest prefill program for
    one period of the Qwen3-Next cell (3 Gated-DeltaNet layers + 1
    attention layer at the published widths, 128 of 512 experts held; 16
    slots of 4096 so that the CPU-side cache stays small), lowered from
    the signatures the engine dispatches and compiled for the described
    chip: the described compile that ``benchmarks/proof/describe_compile.py``
    (a file of older signatures) cannot make.  The kernels by name, one
    of each a layer and program (the grouped matmul two a layer in each
    of an expert layer's two layouts: the one sized by the rows held and
    its full-size fallback); the 4-D state written in place."""
    import torchdistx_tpu as tdx
    from torchdistx_tpu.generation import SLOT_STATE_ROWS
    from torchdistx_tpu.models import Qwen3Next
    from torchdistx_tpu.serve import ServeEngine

    (chip,) = one_chip.device_set
    slots, bucket = 16, 3072
    model = tdx.deferred_init(
        lambda: Qwen3Next.from_name(
            "qwen3_next_80b_a3b", n_layers=4, vocab_size=37984,
            max_seq_len=Q_L, experts_held=(0, Q_HELD),
        )
    )
    engine = ServeEngine(  # its (small) cache lives on the CPU
        model, num_slots=slots, max_len=Q_L, prefill_buckets=(bucket,),
        cost_cards=False,
    )
    assert engine.cache.state_slot_bytes == 3 * (2097152 + 49152)
    assert engine.cache.kv_row_bytes == 2048

    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def ints(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=one_chip)

    params = {n: shape(p) for n, p in model.named_parameters()}
    kv = jax.tree_util.tree_map(shape, engine.cache.kv)
    # ``interpret=None`` and ``use_flash`` ask jax.devices()[0].platform
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [chip])
    decode = engine._decode_program().lower(
        params, kv, ints(SLOT_STATE_ROWS, slots), ints(slots),
        ints(SLOT_STATE_ROWS + 1, slots),
    ).compile().as_text()
    names = _kernel_names(decode)
    assert sorted(set(names)) == [
        "tdx_decode_attention", "tdx_gated_delta_update", "tdx_grouped_matmul"]
    assert (names.count("tdx_gated_delta_update"), names.count(
        "tdx_decode_attention"), names.count("tdx_grouped_matmul")) == (3, 1, 16)
    state = slots * Q_HV * Q_D * Q_D
    copies = [
        f"{name} = {type_} {op}"
        for _, name, op, type_, _ in _cache_sized(decode, state)
        if op in ("copy", "transpose")
    ]
    assert not copies, copies
    prefill = engine._prefill_program(bucket).lower(
        params, kv, ints(slots), ints(1, bucket), ints(), ints(),
        jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one_chip), ints(1),
    ).compile().as_text()
    names = _kernel_names(prefill)
    assert (names.count("tdx_gated_delta_chunk"), names.count(
        FLASH_FORWARD), names.count("tdx_grouped_matmul")) == (3, 1, 16)


# deepseek-coder-1.3b's train step at its published widths (the train
# cell's): hidden 2048, 16 heads of 128, FFN 5504, vocab 32256, a step of
# 4 x 2048 tokens under AnyPrecisionAdamW
DS_DIM, DS_FFN, DS_VOCAB, DS_ROWS, DS_SEQ = 2048, 5504, 32256, 4, 2048


def _dscoder_step(one_chip, monkeypatch, n_layers, auto):
    """The train step of ``n_layers`` blocks whose checkpoints resolve
    "auto" to ``auto``, compiled for the described chip."""
    import numpy as np
    from jax.sharding import Mesh

    import torchdistx_tpu as tdx
    from torchdistx_tpu.models import Llama
    from torchdistx_tpu.models.llama import LlamaConfig
    from torchdistx_tpu.nn import functional, functional_call
    from torchdistx_tpu.optimizers import anyprecision_adamw
    from torchdistx_tpu.parallel import ShardedTrainStep
    from torchdistx_tpu.parallel.fsdp import optimizer_state_shardings

    (chip,) = one_chip.device_set
    mesh = Mesh(np.array([chip]), ("fsdp",))
    cfg = LlamaConfig(
        vocab_size=DS_VOCAB, dim=DS_DIM, n_layers=n_layers, n_heads=16,
        ffn_dim=DS_FFN, max_seq_len=DS_SEQ, rope_theta=1e5, norm_eps=1e-6,
        dtype=jnp.bfloat16, remat=True,
    )
    model = tdx.deferred_init(lambda: Llama(cfg))
    opt = anyprecision_adamw(1e-4)
    step = ShardedTrainStep(
        lambda p, b: functional.cross_entropy(
            functional_call(model, p, (b[0],)), b[1]),
        opt, mesh, shard_axis="fsdp",
    )
    params = {
        n: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip)
        for n, p in model.named_parameters()
    }
    state = jax.eval_shape(opt.init, params)
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, optimizer_state_shardings(state, params, mesh),
    )
    rows = jax.ShapeDtypeStruct((DS_ROWS, DS_SEQ), jnp.int32, sharding=one_chip)
    # ``use_flash=None`` and ``interpret=None`` ask jax.devices()[0].platform
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [chip])
    step._build(params, state)
    return step._jit_under(auto).lower(
        params, state, (rows, rows),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()


def test_dscoder_step_keeps_its_blocks_outputs_by_count(one_chip, monkeypatch):
    """Under the saving choice each block keeps, for the backward, its
    input and six matmul outputs (q, k, v, o; gate, up: 21,248 values a
    token) in bf16, and flash's named residuals (``out``, bf16, and the
    per-row ``lse``, float32): the compiled step's temporaries grow by
    that count a layer, within 10 %.  Read between 4 and 6 layers: below
    4 the step's peak is the loss's and the update's, not the kept
    rows'.  Flash's forward runs once a layer (full recompute runs it
    twice)."""
    from torchdistx_tpu.utils import compat

    tokens = DS_ROWS * DS_SEQ
    counted = tokens * (
        2 * (DS_DIM + 4 * DS_DIM + 2 * DS_FFN)  # input, q k v o, gate up
        + 2 * DS_DIM + 4 * 16  # flash's out, and lse for 16 heads
    )
    temps = {}
    for n_layers in (4, 6):
        compiled = _dscoder_step(one_chip, monkeypatch, n_layers, "dots")
        temps[n_layers] = compat.compiled_memory_analysis(compiled)["temp_bytes"]
        names = _kernel_names(compiled.as_text())
        assert names.count(FLASH_FORWARD) == n_layers
        assert sorted(set(names)) == sorted([FLASH_FORWARD, *FLASH_BACKWARD])
    per_layer = (temps[6] - temps[4]) / 2
    assert abs(per_layer / counted - 1) < 0.10, (per_layer, counted)
