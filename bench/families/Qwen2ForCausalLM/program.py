"""The program's entry for a Qwen2-family configuration.

Family code (`family`): maps the configuration file onto the program's
own model configuration, and refuses an entry that is not the
architecture the file describes.
"""

from __future__ import annotations

import dataclasses

from .counts import dims


def program_config(config: dict):
    """The program's model configuration for ``config``: the program's own
    entry for ``arch`` with every size taken from the configuration file."""
    import repro.configs as C

    s = dims(config["model"])
    base = C.get(config["arch"])
    cfg = dataclasses.replace(
        base, n_layers=s["L"], d_model=s["D"], n_heads=s["H"],
        n_kv_heads=s["KV"], head_dim=s["hd"], d_ff=s["F"], vocab_size=s["V"],
        rope_theta=config["model"]["rope_theta"])
    m = config["model"]
    if (cfg.qkv_bias, cfg.tie_embeddings, cfg.act, cfg.norm) != (
            True, m["tie_word_embeddings"], m["hidden_act"], "rmsnorm"):
        raise SystemExit(f"bench: the program's {config['arch']} is not the "
                         f"architecture {config['name']} describes")
    return cfg
