"""Is what the timed path served correct?  The comparison behind ``correct``.

After the window closes, a sample of the requests it finished is drawn
from the seed: the one that reaches deepest into the cache (longest prompt
plus output), one request of the first seed batch, and then others in a
seeded order until at least ``MIN_TOKENS`` served tokens are in the sample.
The reference runs once over each prompt followed by its served tokens
(`gaps`), and the number compared is the widest gap by which a served
token's logit lies below the reference's best logit at its position.
Every served token is greedy, so a correct server reads a gap at rounding
level and a wrong token reads a gap of the logits' own scale.

All of this is shared by every family; the reference's ``logits`` (and
its control) is the family's own (`family`: ``reference.logits``).
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np

from traffic import rng_for

#: Served tokens the sample holds at least ("some hundreds").
MIN_TOKENS = 1024
#: Sequences are padded to a multiple of this, to bound reference compiles.
SEQ_BUCKET = 256


def unserved(requests: Sequence[dict], outputs: Dict[int, np.ndarray],
             vocab: int) -> Set[int]:
    """Requests not served in full: missing, short or long of their
    budget, or holding a token outside the vocabulary."""
    bad = set()
    for r in requests:
        out = outputs.get(r["rid"])
        if (out is None or len(out) != r["max_new_tokens"]
                or (len(out) and (out.min() < 0 or out.max() >= vocab))):
            bad.add(r["rid"])
    return bad


def sample(requests: Sequence[dict], admit_s: Dict[int, float],
           seed: int) -> List[dict]:
    """The requests compared, drawn from ``seed`` (see module docstring).
    ``admit_s`` maps rid to admission time on the engine's clock."""
    longest = max(requests, key=lambda r: (len(r["prompt"])
                                           + r["max_new_tokens"], -r["rid"]))
    first_seed = min(requests, key=lambda r: (admit_s[r["rid"]], r["rid"]))
    picked = [longest] + ([first_seed] if first_seed is not longest else [])
    rest = [r for r in requests
            if r["rid"] not in (longest["rid"], first_seed["rid"])]
    for i in rng_for(seed, 4).permutation(len(rest)):
        if sum(r["max_new_tokens"] for r in picked) >= MIN_TOKENS:
            break
        picked.append(rest[i])
    return picked


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def gaps(logits, m_json, params, tokens, targets, control: bool):
    """Per position, how far the logit of ``targets`` lies below the
    reference's best (0 where the target is the reference's argmax);
    ``logits`` is the family's reference, ``m_json`` the whole ``model``
    section as JSON (hashable, nested groups such as ``rope_scaling``
    included).

    tokens, targets: [S] int32; positions with ``targets < 0`` read 0.
    With ``control``, also the same gap of the token the family's control
    puts first.  Returns ([S] program gaps, [S] control gaps or zeros)."""
    m = json.loads(m_json)
    ref = logits(m, params, tokens)
    best = jnp.max(ref, axis=-1)
    valid = targets >= 0
    at = jnp.take_along_axis(ref, jnp.maximum(targets, 0)[:, None], -1)[:, 0]
    prog = jnp.where(valid, best - at, 0.0)
    if not control:
        return prog, jnp.zeros_like(prog)
    ctl_tok = jnp.argmax(logits(m, params, tokens, control=True), axis=-1)
    ctl = jnp.take_along_axis(ref, ctl_tok[:, None], -1)[:, 0]
    return prog, jnp.where(valid, best - ctl, 0.0)


def widest_gaps(fam, m: dict, params, picked: Sequence[dict],
                outputs: Dict[int, np.ndarray], control: bool = False):
    """(program's widest gap, control's widest gap or 0.0, tokens compared)
    over the requests ``picked``, against family ``fam``'s reference."""
    m_json = json.dumps(m, sort_keys=True)
    prog = ctl = 0.0
    n = 0
    for r in picked:
        prompt, served = r["prompt"], np.asarray(outputs[r["rid"]], np.int32)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        length = -(-len(seq) // SEQ_BUCKET) * SEQ_BUCKET
        tokens = np.zeros(length, np.int32)
        tokens[:len(seq)] = seq
        targets = np.full(length, -1, np.int32)
        targets[len(prompt) - 1:len(seq)] = served
        g, c = gaps(fam.reference.logits, m_json, params,
                    jnp.asarray(tokens), jnp.asarray(targets), control)
        prog = max(prog, float(jnp.max(g)))
        ctl = max(ctl, float(jnp.max(c)))
        n += len(served)
    return prog, ctl, n
