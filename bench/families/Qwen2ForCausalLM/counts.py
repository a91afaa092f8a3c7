"""Operations and minimal HBM bytes of the Qwen2 family (a dense GQA
decoder), from its shapes.

Family code (`family`).  The readers (`decode_roofline`, `mfu`) are
shared: they read only `window_work`'s ``prefill_flops``, ``decode_flops``
and ``decode_bytes``, through the run's ``work``.

Everything here is computed from the configuration file's ``model`` section
(Hugging Face key names), never from the program under test, so a change to
the program cannot change the yardstick.

Conventions, which another family's counts keep for the readers' sake:

* a multiply-add is 2 FLOPs; only matrix products and attention's two
  contractions are counted (norms, RoPE, softmax and SiLU are not);
* the output head (tied to the embedding) is counted once per prefill, for
  the last prompt position, and once per decoded token: the serving path
  needs no other logits;
* minimal bytes of one decode step: every weight read once, each live
  slot's keys and values read over its live context (real tokens only, not
  the left padding, the arena or vacant slots), and one position of keys
  and values written per live slot.  Weights and cache are bf16.
"""

from __future__ import annotations

from typing import Iterable, Sequence

BYTES_PER_ELEMENT = 2          # bf16 weights, activations and KV cache


def dims(m: dict) -> dict:
    """The shapes the counts need, from a config's ``model`` section."""
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    return {"L": m["num_hidden_layers"], "D": d, "H": h,
            "KV": m["num_key_value_heads"],
            "hd": m.get("head_dim", d // h), "F": m["intermediate_size"],
            "V": m["vocab_size"]}


def layer_matmul_params(m: dict) -> int:
    """Weights of one layer's matrix products (attention and MLP)."""
    s = dims(m)
    attn = s["D"] * (s["H"] + 2 * s["KV"]) * s["hd"] + s["H"] * s["hd"] * s["D"]
    return attn + 3 * s["D"] * s["F"]


def param_count(m: dict) -> int:
    """Every parameter: embedding (tied output head), per-layer matrices,
    QKV biases and the two norms, and the final norm."""
    s = dims(m)
    per_layer = (layer_matmul_params(m) + (s["H"] + 2 * s["KV"]) * s["hd"]
                 + 2 * s["D"])
    return s["V"] * s["D"] + s["L"] * per_layer + s["D"]


def weight_bytes(m: dict) -> int:
    return BYTES_PER_ELEMENT * param_count(m)


def kv_bytes_per_token(m: dict) -> int:
    """Keys and values of one position across all layers."""
    s = dims(m)
    return s["L"] * 2 * s["KV"] * s["hd"] * BYTES_PER_ELEMENT


def token_matmul_flops(m: dict, head: bool) -> int:
    """FLOPs of one token through every layer's matrices (and the output
    head when ``head``)."""
    s = dims(m)
    n = s["L"] * layer_matmul_params(m) + (s["V"] * s["D"] if head else 0)
    return 2 * n


def attn_flops(m: dict, ctx: int) -> int:
    """Attention of one query over ``ctx`` keys in every layer: QK^T and PV."""
    s = dims(m)
    return 4 * s["L"] * s["H"] * s["hd"] * ctx


def prefill_flops(m: dict, prompt_len: int) -> int:
    """One prompt of ``prompt_len`` real tokens, causal, head at the end."""
    p = prompt_len
    return (p * token_matmul_flops(m, head=False)
            + 2 * dims(m)["V"] * dims(m)["D"]
            + attn_flops(m, 1) * p * (p + 1) // 2)


def decode_step_flops(m: dict, contexts: Sequence[int]) -> int:
    """One decode step over live slots whose contexts (keys attended,
    including the token being decoded) are ``contexts``."""
    return sum(token_matmul_flops(m, head=True) + attn_flops(m, c)
               for c in contexts)


def decode_step_bytes(m: dict, contexts: Sequence[int]) -> int:
    """Minimal HBM bytes of one decode step (see the module docstring)."""
    kv = kv_bytes_per_token(m)
    return weight_bytes(m) + kv * sum(contexts) + kv * len(contexts)


def window_work(m: dict, requests: Iterable, decode_steps: int) -> dict:
    """Minimal work of a served window.

    ``requests``: (prompt_len, n_tokens) of every request served;
    ``decode_steps``: decode steps the pool executed (each reads the
    weights once, whatever the number of live slots).  Returns FLOPs of
    prefill and decode and the decode phase's minimal bytes."""
    kv = kv_bytes_per_token(m)
    per_tok = token_matmul_flops(m, head=True)
    pf = df = kv_read = n_dec = 0
    for p, n in requests:
        pf += prefill_flops(m, p)
        steps = max(n - 1, 0)
        # sum of contexts p+1 .. p+n-1
        ctx_sum = steps * p + steps * (steps + 1) // 2
        df += steps * per_tok + attn_flops(m, 1) * ctx_sum
        kv_read += kv * ctx_sum
        n_dec += steps
    return {"prefill_flops": pf, "decode_flops": df,
            "decode_bytes": (decode_steps * weight_bytes(m) + kv_read
                             + kv * n_dec),
            "decode_tokens": n_dec}
