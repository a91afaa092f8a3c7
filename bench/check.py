"""Is what the timed path served correct?  The comparison behind ``correct``.

After the window closes, a sample of the requests it finished is drawn
from the seed: the one that reaches deepest into the cache (longest prompt
plus output), one request of the first seed batch, and then others in a
seeded order until at least ``MIN_TOKENS`` served tokens are in the sample.
The reference runs once over each prompt followed by its served tokens
(`reference.gaps`), and the number compared is the widest gap by which a
served token's logit lies below the reference's best logit at its
position.  Every served token is greedy, so a correct server reads a gap
at rounding level and a wrong token reads a gap of the logits' own scale.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Set

import jax.numpy as jnp
import numpy as np

import reference
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


def widest_gaps(m: dict, params, picked: Sequence[dict],
                outputs: Dict[int, np.ndarray], control: bool = False):
    """(program's widest gap, control's widest gap or 0.0, tokens compared)
    over the requests ``picked``."""
    items = tuple(sorted((k, v) for k, v in m.items()
                         if isinstance(v, (bool, int, float, str))))
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
        g, c = reference.gaps(items, params, jnp.asarray(tokens),
                              jnp.asarray(targets), control)
        prog = max(prog, float(jnp.max(g)))
        ctl = max(ctl, float(jnp.max(c)))
        n += len(served)
    return prog, ctl, n
