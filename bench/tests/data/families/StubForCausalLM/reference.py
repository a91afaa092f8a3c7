"""The stub family's reference: the Qwen2 family's (`program.QWEN2`),
behind a read of a nested group of the configuration (``rope_scaling``),
so that a run fails if shared code hands the reference less than the
whole ``model`` section."""

from .program import QWEN2

#: The stub's program applies no rotary scaling; its file says so.
UNSCALED = {"type": "linear", "factor": 1.0}


def logits(m, params, tokens, control=False):
    if m["rope_scaling"] != UNSCALED:
        raise ValueError(f"stub: rope_scaling {m['rope_scaling']!r} is not "
                         f"{UNSCALED!r}")
    return QWEN2.reference.logits(m, params, tokens, control=control)
