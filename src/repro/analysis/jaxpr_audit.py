"""Stage 2: jaxpr contract audit over the real entry points.

Static AST lint cannot see through dynamic dispatch (``bundle.prefill``
resolves at runtime), so this stage traces the actual hot-path entry
points on tiny shapes with `jax.make_jaxpr` and asserts machine-readable
contracts on the result:

* **A101 — no host callbacks**: zero ``pure_callback`` / ``io_callback``
  / ``debug_callback`` primitives anywhere in the jaxpr (recursively
  through scan/while/cond/pjit sub-jaxprs).  A planted
  ``jax.debug.callback`` in a decode body fails here.
* **A102 — no float64**: no aval anywhere carries float64 (x64 leaks
  double memory traffic into the measured path).
* **A103 — fp32 accumulation**: every ``exp`` (softmax core) runs in
  >= 32-bit floats, and prefill/decode logits leave the model as f32.
* **A104 — primitive budget**: the recursive equation count per entry
  point must stay within ``analysis_budgets.json``.  The report always
  shows the diff against the last observed count (not just the
  threshold), so a +40% jaxpr is visible in review even while under
  budget; ``--update-budgets`` re-baselines.
* **A105 — retrace audit**: re-runs the engine across the documented
  shape-relevant axes (prompt buckets, batch arms) and the explicitly
  non-shape-relevant ones (prompt content, raggedness within a bucket,
  round index, continuous-batching occupancy churn) and fails if any
  jit cache grows on the latter — or if the decode loop retraces on
  the prompt bucket, whose start position is contractually traced.
* **A106 — traceability**: the entry point must trace at all; a
  ``.item()`` / ``float()`` planted in a traced body raises a
  concretization error that lands here.

Entry points: every family in `FAMILIES` (one representative per model
family, same list the engine differential tests pin) gets
``prefill`` + ``decode_step``; the engine contributes its one decode
loop (the slot-pool loop it serves) and the admission prefill.
"""

from __future__ import annotations

import json
import math
import os
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.analysis.findings import Finding

# One representative per model family (dense/GQA transformer, RWKV
# recurrence, mixed recurrent/attention, softcap+sliding-window, MoE) —
# keep in sync with tests/test_engine_fused.py::FAMILIES.
FAMILIES = ["smollm-360m", "rwkv6-3b", "recurrentgemma-9b",
            "gemma2-27b", "mixtral-8x22b"]

DEFAULT_BUDGETS_PATH = os.path.join(os.path.dirname(__file__),
                                    "analysis_budgets.json")

FORBIDDEN_PRIMITIVES = {"pure_callback", "io_callback", "debug_callback",
                        "callback"}

_TINY_BATCH = 2
_TINY_PROMPT = 8
_TINY_SEQ = 24


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(v):
    if hasattr(v, "eqns"):            # raw Jaxpr
        yield v
    elif hasattr(v, "jaxpr"):         # ClosedJaxpr
        yield v.jaxpr
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _sub_jaxprs(x)


def iter_eqns(jaxpr):
    """Every equation, recursively through sub-jaxprs (scan/while/cond
    bodies, pjit calls, custom_* rules)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def count_primitives(closed) -> int:
    return sum(1 for _ in iter_eqns(closed))


def _avals(eqn):
    for var in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(var, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield aval


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def _smoke_bundle(name: str):
    import jax
    import repro.configs as C
    from repro.models.registry import bundle_for
    cfg = C.get_smoke(name)
    bundle = bundle_for(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return bundle, params


def family_entry_thunks(families: Optional[List[str]] = None,
                        bundles: Optional[Dict[str, object]] = None,
                        ) -> Dict[str, Callable[[], object]]:
    """{entry_name: thunk returning a ClosedJaxpr} for each family's
    prefill/decode_step on tiny shapes.  `bundles` overrides the
    (bundle, params) pair per family — the audit's own tests inject
    sabotaged bundles through it."""
    import jax
    import jax.numpy as jnp

    thunks: Dict[str, Callable[[], object]] = {}
    for name in (families if families is not None else FAMILIES):

        def make(name=name):
            if bundles and name in bundles:
                bundle, params = bundles[name]
            else:
                bundle, params = _smoke_bundle(name)
            b, lp, s = _TINY_BATCH, _TINY_PROMPT, _TINY_SEQ
            toks = jnp.ones((b, lp), jnp.int32)
            pmask = jnp.ones((b, lp), bool)
            dmask = jnp.ones((b, s), bool)
            cache = bundle.init_cache(b, s)
            tok = jnp.ones((b,), jnp.int32)
            pos = jnp.asarray(lp, jnp.int32)
            return bundle, params, toks, pmask, dmask, cache, tok, pos

        def prefill_thunk(name=name, make=make):
            bundle, params, toks, pmask, _d, cache, _t, _p = make()
            return jax.make_jaxpr(
                lambda p, t, c, m: bundle.prefill(p, t, c, attn_mask=m)
            )(params, toks, cache, pmask)

        def decode_thunk(name=name, make=make):
            bundle, params, _t, _pm, dmask, cache, tok, pos = make()
            return jax.make_jaxpr(
                lambda p, t, c, i, m: bundle.decode_step(p, t, c, i,
                                                         attn_mask=m)
            )(params, tok, cache, pos, dmask)

        thunks[f"{name}/prefill"] = prefill_thunk
        thunks[f"{name}/decode_step"] = decode_thunk
    return thunks


def default_engine_factory():
    """Tiny smollm engine for the engine-loop entries and the retrace
    audit (prompt_bucket=8 so two buckets fit the arena)."""
    import jax
    import repro.configs as C
    from repro.models.registry import bundle_for
    from repro.serving.engine import InferenceEngine
    cfg = C.get_smoke("smollm-360m")
    bundle = bundle_for(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return InferenceEngine(bundle, params, max_batch=4, max_seq_len=64,
                           prompt_bucket=8)


def engine_entry_thunks(engine_factory: Optional[Callable] = None,
                        ) -> Dict[str, Callable[[], object]]:
    """The slot-pool decode loop and the admission prefill of the
    serving engine."""
    import jax
    import jax.numpy as jnp

    factory = engine_factory or default_engine_factory

    def _setup():
        eng = factory()
        b, s = 2, eng.max_seq_len
        cache = eng.bundle.init_cache(b, s)
        tok = jnp.ones((b,), jnp.int32)
        mask = jnp.ones((b, s), bool)
        start = jnp.asarray(8, jnp.int32)
        return eng, cache, tok, mask, start

    def continuous(_s=_setup):
        eng, cache, tok, mask, start = _s()
        b = tok.shape[0]
        fin = jnp.zeros((b,), bool)
        rem = jnp.full((b,), 4, jnp.int32)
        return jax.make_jaxpr(eng._fused_continuous_fn,
                              static_argnums=(10,))(
            eng.params, tok, cache, mask, start, fin, rem,
            jnp.asarray(-1, jnp.int32), jnp.asarray(4, jnp.int32),
            jnp.asarray(0, jnp.int32), 4)

    def admit(_s=_setup):
        eng, cache, _tok, _mask, _start = _s()
        toks1 = jnp.ones((1, 8), jnp.int32)
        mask1 = jnp.ones((1, 8), bool)
        return jax.make_jaxpr(eng._admit_fn)(
            eng.params, toks1, mask1, cache,
            jnp.asarray(0, jnp.int32), jnp.asarray(8, jnp.int32))

    return {"engine/continuous_decode": continuous,
            "engine/admit_prefill": admit}


# ---------------------------------------------------------------------------
# Contracts
# ---------------------------------------------------------------------------


def check_jaxpr_contracts(entry: str, closed,
                          check_logits: bool = False) -> List[Finding]:
    """A101 (callbacks) + A102 (f64) + A103 (fp32 accumulation) on one
    traced entry point."""
    out: List[Finding] = []
    seen_cb = set()
    for eqn in iter_eqns(closed):
        pname = eqn.primitive.name
        if pname in FORBIDDEN_PRIMITIVES and pname not in seen_cb:
            seen_cb.add(pname)
            out.append(Finding(
                rule="A101", path="", line=0, stage="audit", entry=entry,
                message=f"host callback primitive '{pname}' in the "
                        f"traced graph of {entry}",
                hint="callbacks sync the device every call; remove them "
                     "from the hot path (obs hooks belong outside jit)"))
        for aval in _avals(eqn):
            if str(aval.dtype) == "float64":
                out.append(Finding(
                    rule="A102", path="", line=0, stage="audit",
                    entry=entry,
                    message=f"float64 aval ({pname}) in {entry}",
                    hint="keep device math in f32; f64 belongs to host "
                         "accounting only"))
                break
        if pname == "exp":
            for aval in _avals(eqn):
                if aval.dtype.kind == "f" and aval.dtype.itemsize < 4:
                    out.append(Finding(
                        rule="A103", path="", line=0, stage="audit",
                        entry=entry,
                        message=f"softmax exp accumulates in "
                                f"{aval.dtype} in {entry}",
                        hint="upcast attention scores to f32 before "
                             "exp (flash kernels already do)"))
                    break
    if check_logits:
        dt = closed.out_avals[0].dtype
        if str(dt) != "float32":
            out.append(Finding(
                rule="A103", path="", line=0, stage="audit", entry=entry,
                message=f"logits leave {entry} as {dt}, not float32",
                hint="argmax/sampling must see f32 logits; cast at the "
                     "unembed"))
    # Deduplicate A102 per entry (one finding is enough to fail).
    deduped, keys = [], set()
    for f in out:
        k = (f.rule, f.entry) if f.rule == "A102" else (f.rule, f.entry,
                                                        f.message)
        if k not in keys:
            keys.add(k)
            deduped.append(f)
    return deduped


def load_budgets(path: str = DEFAULT_BUDGETS_PATH) -> Dict[str, dict]:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        return {}


def write_budgets(budgets: Dict[str, dict],
                  path: str = DEFAULT_BUDGETS_PATH) -> None:
    with open(path, "w") as fh:
        json.dump(budgets, fh, indent=2, sort_keys=True)
        fh.write("\n")


def check_budget(entry: str, count: int, budgets: Dict[str, dict],
                 ) -> Tuple[List[Finding], dict]:
    """A104 + the diff row for the report table."""
    b = budgets.get(entry)
    if b is None:
        row = {"count": count, "observed": None, "budget": None,
               "status": "NEW (run --update-budgets)"}
        return [Finding(
            rule="A104", path="", line=0, stage="audit", entry=entry,
            message=f"no primitive budget recorded for {entry} "
                    f"(count {count})",
            hint="python -m repro.analysis --update-budgets commits a "
                 "reviewable baseline")], row
    observed, budget = b.get("observed"), b.get("budget")
    row = {"count": count, "observed": observed, "budget": budget,
           "status": "ok"}
    findings: List[Finding] = []
    if budget is not None and count > budget:
        row["status"] = "OVER BUDGET"
        findings.append(Finding(
            rule="A104", path="", line=0, stage="audit", entry=entry,
            message=f"{entry} traced to {count} primitives, budget is "
                    f"{budget} (last observed {observed})",
            hint="either shrink the graph or justify the growth and "
                 "run --update-budgets (the diff lands in review)"))
    elif observed is not None and count != observed:
        row["status"] = f"drift {count - observed:+d}"
    return findings, row


# ---------------------------------------------------------------------------
# Retrace audit
# ---------------------------------------------------------------------------


def retrace_audit(engine_factory: Optional[Callable] = None,
                  ) -> List[Finding]:
    """A105: the jit caches may only grow on shape-relevant axes."""
    from repro.serving.scheduler import EngineRequest

    eng = (engine_factory or default_engine_factory)()
    vocab = eng.bundle.cfg.vocab_size
    rng = np.random.default_rng(0)
    out: List[Finding] = []

    def prompts(lengths):
        return [rng.integers(1, vocab, size=n).astype(np.int32)
                for n in lengths]

    def diff(before: Dict[str, int], after: Dict[str, int]) -> str:
        return ", ".join(f"{k}: {before[k]}->{after[k]}"
                         for k in sorted(after)
                         if after[k] != before.get(k, 0))

    # Warm-up: compile (batch=2, bucket=8).
    eng.generate(prompts([5, 7]), 4)
    base = dict(eng.compile_counts)

    # Non-shape-relevant axes: prompt content, raggedness within the
    # bucket, round index.  Nothing may compile.
    eng.generate(prompts([3, 6]), 4)
    eng.generate(prompts([5, 7]), 4)
    flat = dict(eng.compile_counts)
    if flat != base:
        out.append(Finding(
            rule="A105", path="", line=0, stage="audit",
            entry="engine/static",
            message="jit cache grew on a non-shape-relevant axis "
                    "(prompt content / raggedness within bucket / "
                    f"round): {diff(base, flat)}",
            hint="something in the hot path keys a trace on values; "
                 "find the leaked python scalar/shape"))

    # Prompt bucket is shape-relevant for *prefill only*: the decode
    # loop takes the start position as a traced scalar, so a new
    # bucket must not retrace it.
    eng.generate(prompts([9, 12]), 4)          # bucket 16
    bucket = dict(eng.compile_counts)
    for key in ("decode_continuous", "admit"):
        if bucket.get(key, 0) != flat.get(key, 0):
            out.append(Finding(
                rule="A105", path="", line=0, stage="audit",
                entry="engine/static",
                message=f"'{key}' retraced on the prompt bucket "
                        f"({diff(flat, bucket)}) — the start position "
                        "is contractually a traced scalar",
                hint="check static_argnums on the decode jits: only "
                     "the step/chunk count is static"))
    if bucket.get("prefill", 0) > flat.get("prefill", 0) + 1:
        out.append(Finding(
            rule="A105", path="", line=0, stage="audit",
            entry="engine/static",
            message="prefill compiled more than once for one new "
                    f"prompt bucket: {diff(flat, bucket)}",
            hint="prefill must key on (batch, bucket) only"))

    # Batch arm is shape-relevant: allowed to add exactly one entry per
    # jit (and one cache-pool row).
    eng.generate(prompts([5, 7, 6]), 4)
    batch = dict(eng.compile_counts)
    for key in ("prefill", "decode_continuous"):
        if batch.get(key, 0) > bucket.get(key, 0) + 1:
            out.append(Finding(
                rule="A105", path="", line=0, stage="audit",
                entry="engine/static",
                message=f"'{key}' compiled more than once for one new "
                        f"batch arm: {diff(bucket, batch)}",
                hint="the batch axis must be the only new shape"))

    # Continuous batching: slot churn / occupancy / budgets are value
    # axes — after the first serve compiles the loop, a differently
    # shaped workload (same buckets, same slot width) must be free.
    def reqs(budgets, stagger):
        return [EngineRequest(rid=i, prompt=p, max_new_tokens=m,
                              arrival_s=i * stagger)
                for i, (p, m) in enumerate(zip(prompts([5, 7, 6, 4]),
                                               budgets))]

    eng.generate_continuous(reqs([3, 5, 2, 4], 0.0), n_slots=2,
                            chunk=4, step_time_s=0.01)
    warm = dict(eng.compile_counts)
    eng.generate_continuous(reqs([2, 2, 6, 3], 0.05), n_slots=2,
                            chunk=4, step_time_s=0.01)
    churn = dict(eng.compile_counts)
    if churn != warm:
        out.append(Finding(
            rule="A105", path="", line=0, stage="audit",
            entry="engine/continuous",
            message="jit cache grew on continuous-batching occupancy "
                    f"churn: {diff(warm, churn)}",
            hint="slot index, clock offset, budgets and pending count "
                 "must all be traced scalars"))
    return out


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_audit(budgets_path: str = DEFAULT_BUDGETS_PATH,
              update_budgets: bool = False,
              families: Optional[List[str]] = None,
              bundles: Optional[Dict[str, object]] = None,
              engine_factory: Optional[Callable] = None,
              include_retrace: bool = True,
              include_engine: bool = True,
              ) -> Tuple[List[Finding], Dict[str, dict]]:
    """Trace every entry point and apply every contract.  Returns
    (findings, budget_rows).  `bundles`/`engine_factory` are test
    injection points for sabotaged models."""
    budgets = load_budgets(budgets_path)
    findings: List[Finding] = []
    rows: Dict[str, dict] = {}
    new_budgets: Dict[str, dict] = {}

    thunks = dict(family_entry_thunks(families=families, bundles=bundles))
    if include_engine:
        thunks.update(engine_entry_thunks(engine_factory=engine_factory))

    for entry in sorted(thunks):
        try:
            closed = thunks[entry]()
        except Exception as e:  # noqa: BLE001 — any trace failure is the finding
            msg = " ".join(str(e).split())[:200]
            findings.append(Finding(
                rule="A106", path="", line=0, stage="audit", entry=entry,
                message=f"entry point failed to trace: {type(e).__name__}"
                        f": {msg}",
                hint="a host sync (.item()/float()/np.*) or python "
                     "branching on a tracer breaks the trace — see the "
                     "exception"))
            continue
        check_logits = entry.endswith(("/prefill", "/decode_step"))
        findings.extend(check_jaxpr_contracts(entry, closed,
                                              check_logits=check_logits))
        count = count_primitives(closed)
        if update_budgets:
            new_budgets[entry] = {"observed": count,
                                  "budget": int(math.ceil(count * 1.5))}
            rows[entry] = {"count": count, "observed": count,
                           "budget": new_budgets[entry]["budget"],
                           "status": "updated"}
        else:
            bf, row = check_budget(entry, count, budgets)
            findings.extend(bf)
            rows[entry] = row

    if update_budgets:
        write_budgets(new_budgets, budgets_path)

    if include_retrace:
        findings.extend(retrace_audit(engine_factory=engine_factory))
    return findings, rows
