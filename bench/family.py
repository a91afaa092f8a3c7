"""The seam between the harness and an architecture family's code.

A configuration's family is its own ``model["architectures"][0]``, the
Hugging Face class name (``Qwen2ForCausalLM``).  Its code lives in
``bench/families/<name>/``, four files that import one another relatively
(``from .counts import dims``) and the shared modules of ``bench/`` by name:

    program.py    program_config(config): the program's model configuration
                  for the file, refusing an entry of another architecture
    weights.py    make(m, seed), shapes(m): seeded weights (`seeded.make`)
    reference.py  logits(m, params, tokens, control=False): the plain
                  float32 reference of one sequence, and its control
    counts.py     param_count(m), weight_bytes(m), kv_bytes_per_token(m),
                  window_work(m, requests, decode_steps) -> prefill_flops,
                  decode_flops, decode_bytes (what the readers use)

Here ``m`` is the configuration's whole ``model`` section, nested groups
(``rope_scaling``) included; every caller passes it so.  The harness reaches
family code only through `load`, so a configuration of a new family adds
its directory and edits no file that is there.
"""

from __future__ import annotations

import importlib
import importlib.machinery
import importlib.util
import os
import re
import sys
from types import ModuleType
from typing import NamedTuple

#: Where the families live: `load`'s default root.
FAMILIES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "families")


class Family(NamedTuple):
    program: ModuleType
    weights: ModuleType
    reference: ModuleType
    counts: ModuleType


def load(config: dict, root: str = FAMILIES) -> Family:
    """The four modules of ``config``'s family, found under ``root``.
    Each directory is imported once per process, as a package of its own
    named after its path (``root`` need not be on ``sys.path``), so its
    jitted functions keep their caches."""
    name = config["model"]["architectures"][0]
    directory = os.path.join(os.path.abspath(root), name)
    if not os.path.isdir(directory):
        raise FileNotFoundError(
            f"no family {name!r} for configuration "
            f"{config.get('name', '?')!r}: looked for the directory "
            f"{directory}")
    package = "bench_family_" + re.sub(r"\W", "_", directory)
    if package not in sys.modules:
        spec = importlib.machinery.ModuleSpec(package, None, is_package=True)
        module = importlib.util.module_from_spec(spec)
        module.__path__ = [directory]
        sys.modules[package] = module
    return Family(*(importlib.import_module(f"{package}.{part}")
                    for part in Family._fields))
