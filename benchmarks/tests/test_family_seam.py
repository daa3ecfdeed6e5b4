"""Everything that depends on the architecture reaches a run through the
configuration's family, and through nothing else.

(a) A second family that lives only here -- a temporary ``families/stub.py``
    whose reference has a known ``leaf_plan`` and gives known logits, norms
    and counts -- is what both drivers call for ``weights_differ``, ``check``
    and the FLOP counters: a whole rehearsal run comes out with the stub's
    numbers, with no edit outside this test's temporary files.  (The
    program under the stub is still the tiny Llama: a family's
    ``constructor`` is the one thing the stub borrows.)
(b) A family without ``reference`` or ``counts``, or without what a driver
    needs of them, is an error at load that names what is missing.
(c) No file of the harness, the drivers, the metric readers, the proofs or
    ``run.py`` names a symbol of the Llama family.
"""

import json
import os
import re
import shutil

import pytest

from harness import loader

STUB = '''
"""A second family, for the seam's test only."""
import dataclasses

import jax.numpy as jnp

from families import llama

constructor = llama.constructor   # the program's model: borrowed
BEST = 7                          # the token this reference always puts first
BIG = 1e30                        # a norm no program reaches


class reference:
    PRECISIONS = ("f32",)

    @dataclasses.dataclass(frozen=True)
    class Arch:
        vocab_size: int
        hidden_size: int
        dtype: str
        init_std: float

        @classmethod
        def from_config(cls, cfg):
            return cls(int(cfg["vocab_size"]), int(cfg["hidden_size"]),
                       str(cfg["torch_dtype"]), float(cfg["initializer_range"]))

        @property
        def jdtype(self):
            return jnp.dtype(self.dtype)

    @staticmethod
    def leaf_plan(a):
        shape = (a.vocab_size, a.hidden_size)
        # the embedding as the seed makes it; the head under the
        # embedding's counter, which is not its own; no other leaf
        return [("tok_emb.weight", shape, 0), ("lm_head.weight", shape, 0)]

    @staticmethod
    def sample_leaves(a):
        return ("tok_emb.weight",)

    class ServeReference:
        def __init__(self, arch, seed, precision="f32"):
            self.a = arch

        def logits_rows(self, tokens):
            one = jnp.zeros((tokens.shape[1], self.a.vocab_size),
                            jnp.float32).at[:, BEST].set(1.0)
            for i in range(tokens.shape[0]):
                yield i, one

    class TrainReference:
        def __init__(self, arch, seed, adamw, precision="f32", rows=None):
            self.a, self.keep, self.kept = arch, (), {}

        def step(self, tokens, labels):
            self.kept = {n: jnp.full((self.a.vocab_size, self.a.hidden_size),
                                     1e15, jnp.float32) for n in self.keep}
            return 2.0, {"tok_emb.weight": BIG, "lm_head.weight": BIG}

        def change_norms(self):
            return {"tok_emb.weight": BIG, "lm_head.weight": BIG}


class counts:
    @staticmethod
    def serve_flops(cfg, prompt_lens, decode_rows):
        return 4242

    @staticmethod
    def train_flops_per_token(cfg, seq):
        return 777000 + seq
'''


@pytest.fixture
def stub_family(tmp_path, monkeypatch):
    """``families/stub.py`` and a copy of the rehearsal files whose
    configurations name it, all under ``tmp_path``."""
    families = tmp_path / "families"
    families.mkdir()
    (families / "stub.py").write_text(STUB)
    rehearsal = tmp_path / "rehearsal"
    shutil.copytree(loader.REHEARSAL, rehearsal)
    for path in (rehearsal / "configs").iterdir():
        config = json.loads(path.read_text())
        assert config["family"] == "llama"
        config["family"] = "stub"
        path.write_text(json.dumps(config))
    monkeypatch.setattr(loader, "FAMILIES", str(families))
    monkeypatch.setattr(loader, "REHEARSAL", str(rehearsal))
    return families


def test_the_stub_family_decides_a_serve_run(drive, stub_family):
    result = drive("tiny.batch4")
    c = result["compared"]
    # 21 leaves in the tiny Llama: the stub's plan knows two, and its head
    # is not what the seed makes under counter 0
    assert c["weights_differ"]["value"] == 20
    # every served token but BEST lies 1.0 under the stub's best
    assert c["logit_gap"]["value"] == 1.0
    assert 0.9 < c["logit_gap_mean"]["value"] <= 1.0
    assert result["correct"] is False
    assert result["counts"]["serve.flops"] == 4242


def test_the_stub_family_decides_a_train_run(drive, stub_family):
    result = drive("tiny.train")
    c = result["compared"]
    assert c["weights_differ"]["value"] == 20
    # against norms of 1e30 every gap is the whole of it
    assert c["grad_norm_gap"]["value"] == 1.0
    assert c["change_norm_gap"]["value"] == 1.0
    assert c["grad_diff"]["value"] == pytest.approx(1.0, rel=1e-5)
    assert result["correct"] is False
    assert result["counts"]["train.flops_per_token"] == 777000 + 128
    assert set(result["read_not_compared"]) == {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3"}


@pytest.mark.parametrize("missing", ["reference", "counts", "constructor"])
def test_a_family_that_lacks_a_part_fails_at_load(tmp_path, monkeypatch, missing):
    parts = {"reference": "reference = llama.reference",
             "counts": "counts = llama.counts",
             "constructor": "constructor = llama.constructor"}
    del parts[missing]
    (tmp_path / "partial.py").write_text(
        "from families import llama\n" + "\n".join(parts.values()) + "\n")
    monkeypatch.setattr(loader, "FAMILIES", str(tmp_path))
    with pytest.raises(loader.BenchmarkError,
                       match=f"'partial'.*lacks '{missing}"):
        loader.load_family("partial")


def test_a_family_that_lacks_what_a_driver_needs_fails_at_load(stub_family):
    (stub_family / "bare.py").write_text(
        "from families import llama\nconstructor = llama.constructor\n"
        "class reference:\n    Arch = llama.reference.Arch\n"
        "    leaf_plan = llama.reference.leaf_plan\n    PRECISIONS = ('f32',)\n"
        "class counts:\n    pass\n")
    assert loader.load_family("bare").constructor
    for need in ("reference.ServeReference", "counts.serve_flops",
                 "reference.TrainReference", "counts.train_flops_per_token"):
        with pytest.raises(loader.BenchmarkError, match=f"'bare'.*lacks '{need}'"):
            loader.load_family("bare", needs=(need,))
    with pytest.raises(loader.BenchmarkError, match="unknown model family"):
        loader.load_family("no-such-family")


def test_the_llama_family_brings_what_both_drivers_need():
    family = loader.load_family("llama", needs=(
        "reference.ServeReference", "reference.TrainReference",
        "reference.sample_leaves", "counts.serve_flops",
        "counts.train_flops_per_token"))
    assert set(family.reference.PRECISIONS) == {"f32", "bf16", "int8"}
    for cfg in ("dscoder-1.3b-1chip", "mistral-7b-v0.3-1chip"):
        with open(os.path.join(loader.ROOT, "configs", cfg + ".json")) as f:
            arch = family.reference.Arch.from_config(json.load(f))
        plan = family.reference.leaf_plan(arch)
        counters = [c for _, _, c in plan if c is not None]
        assert counters == list(range(len(counters)))
        assert len(plan) == 9 * arch.num_hidden_layers + 3


#: what only the Llama family may say: its modules, its leaves, its
#: mathematics, its parameter counts
LLAMA_ONLY = re.compile(
    r"[Ll]lama|\bArch\b|ServeReference\(|TrainReference\(|block_matrices|BLOCK_LEAVES"
    r"|block_weights_from_seed|rope_tables|\brope\(|head_logits|head_loss"
    r"|matmul_params|total_params|tok_emb|lm_head|attn\.w[qkvo]|mlp\.w_")
#: the seam: a call through the family, or a name of the protocol in quotes
THROUGH_FAMILY = re.compile(
    r"family(\(\))?\.(reference|counts)\.\w+|family_ref\.\w+"
    r"|\"(reference|counts)\.\w+\"")


def architecture_free_files():
    files = [os.path.join(loader.ROOT, "run.py")]
    for sub in ("harness", "drivers", "metrics", "proof"):
        d = os.path.join(loader.ROOT, sub)
        files += [os.path.join(d, f) for f in sorted(os.listdir(d))
                  if f.endswith(".py")]
    return files


@pytest.mark.parametrize("path", architecture_free_files(),
                         ids=lambda p: os.path.relpath(p, loader.ROOT))
def test_no_llama_outside_its_family(path):
    with open(path) as f:
        for n, line in enumerate(f, 1):
            rest = THROUGH_FAMILY.sub("", line)
            found = LLAMA_ONLY.search(rest)
            assert not found, (
                f"{os.path.relpath(path, loader.ROOT)}:{n} names "
                f"{found.group(0)!r}: architecture belongs under families/")
