"""The seam per architecture family (`bench/family.py`), on the CPU.

Through the loader the Qwen2 family gives the weights, counts and
reference it gave before it moved into ``bench/families/``; a stub family
that lives only under ``tests/data/families/`` is found by pointing the
loader there and serves a whole run (`run.run_cell`, chip check skipped)
with no other harness file naming it; an unknown family fails with the
directory it looked for; and no shared harness file imports family code
by name.
"""

import functools
import glob
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import check  # noqa: E402
import family  # noqa: E402
import run  # noqa: E402
from repro.models.registry import bundle_for  # noqa: E402

STUB_ROOT = os.path.join(BENCH, "tests", "data", "families")
STUB = "StubForCausalLM"
TINY_MODEL = {"architectures": ["Qwen2ForCausalLM"], "hidden_act": "silu",
              "hidden_size": 64, "intermediate_size": 160,
              "num_attention_heads": 4, "num_hidden_layers": 2,
              "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
              "rope_theta": 1e6, "tie_word_embeddings": True,
              "vocab_size": 256}
TINY = {"name": "tiny", "arch": "qwen2-1.5b", "model": TINY_MODEL,
        "engine": {"n_slots": 4, "max_seq_len": 256, "prompt_bucket": 16,
                   "chunk": 4},
        "correct": {"widest_gap": 0.03}}
STUB_TINY = dict(TINY, name="stub-tiny",
                 model=dict(TINY_MODEL, architectures=[STUB],
                            rope_scaling={"type": "linear", "factor": 1.0}))
MIX = {"arrivals": "backlog", "rate_rps": 20.0,
       "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                  "min": 4, "max": 48},
       "output": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                  "min": 8, "max": 32}}


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _read(path) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    return h.hexdigest()


# sha256 over (leaf path, bf16 bits) of `weights.make(TINY_MODEL, seed)`,
# taken before the Qwen2 files moved into bench/families/.
@pytest.mark.parametrize("seed,digest", [
    (2 ** 31 + 7,
     "87a10e3e3d710a4478a13589c83e4a721bca68c8d9e5224ed064b5dc5eb7a6ce"),
    (-5, "eb7567c0dc414e0a740c43709b2322546b404de9243265079197ad7faa12cb69"),
    (2 ** 40 + 3,
     "b25f96c850549392c878380004bab39d9f62dffd1995ce0c8dd83085bf248b08"),
])
def test_qwen2_weights_are_the_bytes_they_were(seed, digest):
    fam = family.load(TINY)
    assert _digest(fam.weights.make(TINY_MODEL, seed)) == digest


# `window_work` of four requests (prompt, tokens) over 1,040 pool steps,
# as the counts gave it before they moved.
@pytest.mark.parametrize("name,want", [
    ("qwen2-1.5b", (3598148812800, 3307936210944, 3226145222656)),
    ("qwen2.5-3b", (7578591625216, 6586828423168, 6438320361472)),
])
def test_qwen2_window_work_is_what_it_was(name, want):
    config = _config(name)
    counts = family.load(config).counts
    work = counts.window_work(config["model"],
                              [(10, 4), (300, 17), (1024, 1), (1, 1024)],
                              1040)
    assert (work["prefill_flops"], work["decode_flops"],
            work["decode_bytes"], work["decode_tokens"]) == (*want, 1042)


def test_qwen2_reference_follows_the_programs_prefill_then_decode():
    """The program prefills a prompt and decodes greedily; the family's
    reference, over the prompt and the served tokens, gives the same
    logits at each position to the tiny cell's limit, and the widest gap
    `check` reads is under it."""
    fam = family.load(TINY)
    bundle = bundle_for(fam.program.program_config(TINY))
    params = fam.weights.make(TINY_MODEL, 3)
    prompt = np.arange(1, 21, dtype=np.int32) * 7 % 255 + 1
    cache = bundle.init_cache(1, 64)
    logits, cache = jax.jit(bundle.prefill)(params, jnp.asarray(prompt)[None],
                                            cache)
    decode_step = jax.jit(bundle.decode_step)
    served, rows = [], []
    for pos in range(len(prompt), len(prompt) + 16):
        rows.append(logits[0].astype(jnp.float32))
        served.append(int(jnp.argmax(logits[0])))
        logits, cache = decode_step(
            params, jnp.asarray(served[-1:], jnp.int32), cache,
            jnp.asarray(pos, jnp.int32))
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    ref = fam.reference.logits(TINY_MODEL, params, jnp.asarray(seq))
    limit = TINY["correct"]["widest_gap"]
    np.testing.assert_allclose(np.asarray(jnp.stack(rows)),
                               np.asarray(ref[len(prompt) - 1:]), atol=limit)
    gap, _, n = check.widest_gaps(fam, TINY_MODEL, params,
                                  [{"rid": 0, "prompt": prompt}],
                                  {0: np.asarray(served, np.int32)})
    assert n == 16 and gap <= limit


def test_stub_family_is_found_under_its_root():
    stub = family.load(STUB_TINY, root=STUB_ROOT)
    for module in stub:
        assert os.path.dirname(module.__file__) == os.path.join(STUB_ROOT,
                                                                STUB)
    assert stub.program.program_config(STUB_TINY).n_layers == 2
    params = stub.weights.make(STUB_TINY["model"], 7)
    assert params["embedding"].shape == (256, 64)
    tokens = jnp.arange(1, 9, dtype=jnp.int32)
    assert stub.reference.logits(STUB_TINY["model"], params,
                                 tokens).shape == (8, 256)
    work = stub.counts.window_work(STUB_TINY["model"], [(10, 4)], 3)
    assert {"prefill_flops", "decode_flops", "decode_bytes"} <= set(work)
    assert family.load(STUB_TINY, root=STUB_ROOT) == stub


def test_stub_family_serves_a_whole_run(monkeypatch):
    """With the loader's root pointed at the stub's, a whole run of a
    configuration that names the stub goes through its four files, and
    the reference gets the nested ``rope_scaling`` group it reads."""
    stub = family.load(STUB_TINY, root=STUB_ROOT)
    calls = []

    def spy(module, name):
        real = getattr(module, name)

        @functools.wraps(real)
        def wrapped(*args, **kw):
            calls.append(name)
            return real(*args, **kw)
        monkeypatch.setattr(module, name, wrapped)

    for module, name in [(stub.program, "program_config"),
                         (stub.weights, "make"),
                         (stub.reference, "logits"),
                         (stub.counts, "window_work")]:
        spy(module, name)
    monkeypatch.setattr(family, "load",
                        functools.partial(family.load, root=STUB_ROOT))
    monkeypatch.setattr(run, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    plan = {"cell": {"chips": 1}, "config": STUB_TINY, "mix": MIX,
            "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"}],
            "per_layer": []}
    res = run.run_cell(plan, 2 ** 31 + 11, 1.0, False, 0.0)
    assert res["correct"] is True and res["failed"] == 0
    assert set(calls) == {"program_config", "make", "logits", "window_work"}


def test_no_other_harness_file_names_the_stub():
    stub_dir = os.path.join(STUB_ROOT, STUB)
    files = glob.glob(os.path.join(BENCH, "**", "*"), recursive=True)
    files.append(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    naming = [path for path in files
              if os.path.isfile(path) and not path.endswith(".pyc")
              and not path.startswith(stub_dir + os.sep)
              and path != os.path.abspath(__file__)
              and STUB.encode() in _read(path)]
    assert naming == []


def test_unknown_family_names_the_directory_it_looked_for(tmp_path):
    config = {"name": "x", "model": {"architectures": ["NoSuchForCausalLM"]}}
    with pytest.raises(FileNotFoundError,
                       match=re.escape(str(tmp_path / "NoSuchForCausalLM"))):
        family.load(config, root=str(tmp_path))
    with pytest.raises(FileNotFoundError, match=re.escape(family.FAMILIES)):
        family.load(config)


def test_harness_reaches_family_code_only_through_the_loader():
    shared = (glob.glob(os.path.join(BENCH, "*.py"))
              + glob.glob(os.path.join(BENCH, "metrics", "*.py")))
    by_name = re.compile(r"^\s*(import|from)\s+(weights|reference|counts|"
                         r"program)\b", re.M)
    assert [p for p in shared if by_name.search(_read(p).decode())] == []
