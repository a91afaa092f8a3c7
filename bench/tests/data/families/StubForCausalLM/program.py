"""A stub family for the loader's tests: the Qwen2 family's code under
another class name, found only by `family.load` with ``root`` set here."""

import os

import family

QWEN2 = family.load({"model": {"architectures": ["Qwen2ForCausalLM"]}},
                    os.path.join(os.path.dirname(family.__file__), "families"))
program_config = QWEN2.program.program_config
