"""Batched JAX inference engine: prefill + fused greedy decode with KV cache.

This is the real-model backend behind the Camel controller (the simulator
estimates (E, L); this engine produces them by actually running a model
on whatever device JAX uses — the CPU for the examples/tests, a TPU for
`chip_smoke.py` and ``serve.py --preset published``).  Latency is
measured wall-clock.  Energy is not measured on a TPU: there is no TPU
power sensor, so `EngineEnvironment` reads a `repro.obs` sensor (Jetson
rails, NVML) where one exists and otherwise the analytical Jetson board
model, with time scaled by that board's DVFS factor — modelled joules,
never a chip measurement.

Hot-path design (what makes the measured (E, L) reflect hardware, not
Python dispatch):

* **Fused decode** — the default decode path is one jitted
  ``lax.fori_loop`` keeping the greedy token, KV cache, and an on-device
  output buffer (``dynamic_update_slice``) inside a single compiled
  computation: one host sync per `generate` call instead of one per
  token.  The per-token Python loop survives as ``decode_impl="loop"``,
  the reference the fused path is asserted bit-identical against.
* **Prompt bucketing** — padded prompt lengths are rounded up to
  ``prompt_bucket`` multiples, so a controller sweep over ragged prompts
  compiles the prefill once per (batch, bucket) instead of once per
  exact length.
* **Cache reuse** — ``init_cache`` buffers are allocated once per batch
  size and reused across `generate` calls (cache shapes depend only on
  (batch, max_seq_len); all updates are functional, so the pooled zero
  buffers are never mutated).  A sweep over batch arms allocates and
  compiles each shape exactly once (`compile_counts` exposes the jit
  cache sizes for the retrace regression test).

Left-padding batches the ragged prompts: all sequences share position
indices so a single prefill call fills the cache, and a boolean pad mask
is threaded through the models' attention (``attn_mask``) so padded
slots are masked rather than attended — ragged and unpadded prompts
produce identical per-sequence logits on attention models (recurrent
families accept and ignore the mask; see their module docstrings).

Continuous batching (``generate_continuous``) reworks the decode phase
around a persistent slot pool sharing one global KV clock:

* **while_loop decode with EOS early-exit** — the fused loop becomes a
  ``lax.while_loop`` carrying per-slot ``(finished, emitted)`` state; it
  stops as soon as every slot is done (EOS or per-request length cap) or
  a slot frees up while admissible requests are pending, so short
  requests stop paying for long co-residents.
* **slot-level admission without retraces** — all live slots decode at
  the same scalar clock ``pos``; a slot's valid KV region is the
  contiguous suffix ``[kv_start, pos)`` of its cache row, expressed via
  the per-row ``attn_mask`` (and therefore via the Pallas decode
  kernel's per-batch ``[kv_start, kv_len)`` windows).  Admitting a
  request is a single-row prefill at ``pos_offset = pos - Lb`` scattered
  into the freed slot (`dynamic_update_slice_in_dim`) plus a mask-row
  update — slot and offset are traced scalars, so slot churn never
  retraces (one trace per prompt bucket).
* **host-side scheduling** — `serving.scheduler.SlotScheduler` owns the
  occupancy/admission/accounting state machine (property-tested in
  isolation); the engine owns the arrays.  The loop runs in chunks of
  ``chunk`` steps: one host sync per chunk to harvest finished slots and
  admit from the `RequestQueue`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.registry import ModelBundle
from repro.obs import EnergyMeter, make_sensor
from repro.obs import tracing as obslog
from repro.platform import BaseEnvironment, DVFSPlatform, Observation, observe
from repro.serving.queueing import require_positive_rate
from repro.serving.requests import ArrivalProcess
from repro.serving.scheduler import (EngineRequest, RequestQueue,
                                     RequestRecord, SlotScheduler,
                                     attribute_energy)


@dataclasses.dataclass
class EngineStats:
    prefill_s: float
    decode_s: float
    tokens_out: int
    decode_impl: str = "fused"

    @property
    def total_s(self) -> float:
        return self.prefill_s + self.decode_s

    @property
    def tokens_per_s(self) -> float:
        """Decode throughput (generated tokens / decode wall-clock)."""
        return self.tokens_out / self.decode_s if self.decode_s > 0 else 0.0


@dataclasses.dataclass
class ContinuousStats(EngineStats):
    """Run-level stats for `generate_continuous`.

    `sim_s` is the simulation-clock duration of the run (wall time scaled
    by `time_scale`, or `step_time_s` units in deterministic mode) —
    goodput is `n_requests / sim_s`.  `records` carries the per-request
    accounting (admit/finish times, queue wait, tokens, joules).

    `phase_s` / `phase_n` hold the seconds and counts of the call's
    `engine.*` spans by name; `chunks` counts decode chunks.  A slot
    still vacant when a chunk starts counts its steps as
    `empty_slot_steps_blocked` when an arrived request waits that the KV
    clock cannot admit, else as `empty_slot_steps_drain`; with the live
    slot-steps (`mean_occupancy × decode_steps`) they sum to
    `n_slots × decode_steps`."""

    sim_s: float = 0.0
    decode_steps: int = 0
    prefill_calls: int = 0
    n_requests: int = 0
    n_cancelled: int = 0
    mean_occupancy: float = 0.0
    mean_queue_wait_s: float = 0.0
    records: List[RequestRecord] = dataclasses.field(default_factory=list)
    # Span time by name (`engine.*` spans: seconds and counts) and the
    # scheduler's counters, incremented at the same boundaries.
    phase_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    phase_n: Dict[str, int] = dataclasses.field(default_factory=dict)
    chunks: int = 0
    empty_slot_steps_blocked: int = 0
    empty_slot_steps_drain: int = 0

    @property
    def goodput_rps(self) -> float:
        """Completed requests per simulated second."""
        return self.n_requests / self.sim_s if self.sim_s > 0 else 0.0


class InferenceEngine:
    """Greedy batched generation with jitted prefill + fused decode.

    decode_impl: "fused" (default — one compiled fori_loop per generate)
    or "loop" (per-token Python loop with a host round-trip per step; the
    reference implementation).  prompt_bucket: padded prompt lengths are
    rounded up to this multiple to bound prefill retraces.
    """

    def __init__(self, bundle: ModelBundle, params, max_batch: int,
                 max_seq_len: int, pad_id: int = 0,
                 decode_impl: str = "fused", prompt_bucket: int = 16):
        if decode_impl not in ("fused", "loop"):
            raise ValueError(f"decode_impl must be 'fused' or 'loop', "
                             f"got {decode_impl!r}")
        if prompt_bucket < 1:
            raise ValueError(f"prompt_bucket must be >= 1, "
                             f"got {prompt_bucket}")
        self.bundle = bundle
        self.params = params
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        self.pad_id = pad_id
        self.decode_impl = decode_impl
        self.prompt_bucket = prompt_bucket

        self._prefill = jax.jit(self._prefill_fn)
        self._decode = jax.jit(self._decode_fn)
        self._fused_decode = jax.jit(self._fused_decode_fn,
                                     static_argnums=(5,))
        self._fused_continuous = jax.jit(self._fused_continuous_fn,
                                         static_argnums=(10,))
        self._admit = jax.jit(self._admit_fn)
        # One zeroed cache tree per batch size, reused across generate
        # calls: prefill/decode are functional (no donation), so pool
        # entries stay all-zero and a batch-arm sweep allocates each
        # shape once.
        self._cache_pool: Dict[int, object] = {}

    # -- single calls --------------------------------------------------------
    # Named functions, not lambdas: a program's name in the profiler's
    # trace (`jit__prefill_fn`) is its function's name.

    def _prefill_fn(self, params, toks, cache, mask):
        """Batched prefill of left-padded prompts at offset 0."""
        return self.bundle.prefill(params, toks, cache, attn_mask=mask)

    def _decode_fn(self, params, tok, cache, pos, mask):
        """One decode step of the whole batch (the `loop` path)."""
        return self.bundle.decode_step(params, tok, cache, pos,
                                       attn_mask=mask)

    # -- fused decode ------------------------------------------------------

    def _fused_decode_fn(self, params, tok, cache, mask, start_pos, steps):
        """One compiled computation for the whole decode phase.

        tok: [B] greedy token from prefill; mask: [B, max_seq_len] pad
        validity over global positions; start_pos: traced scalar (bucketed
        prompt length — changing it does NOT retrace); steps: static.
        Returns the [B, steps] token buffer (single device->host transfer
        at the caller).
        """
        b = tok.shape[0]
        out = jnp.zeros((b, steps), jnp.int32)

        def body(i, carry):
            tok, cache, out = carry
            out = jax.lax.dynamic_update_slice(out, tok[:, None], (0, i))
            logits, cache = self.bundle.decode_step(
                params, tok, cache, start_pos + i, attn_mask=mask)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return tok, cache, out

        _, _, out = jax.lax.fori_loop(0, steps, body, (tok, cache, out))
        return out

    # -- continuous decode -------------------------------------------------

    def _fused_continuous_fn(self, params, tok, cache, mask, start_pos,
                             finished, remaining, eos_id, steps_cap,
                             pending, chunk):
        """One compiled while_loop over up to `chunk` slot-pool decode steps.

        Per-slot carry: `finished` [B] bool (vacant or done slots decode
        but their tokens are masked to -1 and not counted), `emitted` [B]
        int32 (tokens credited this call).  A slot finishes when its
        pre-decode token is `eos_id` (disabled when eos_id < 0) or when
        `emitted` reaches `remaining` (per-slot budget).  The loop exits
        early when every slot is finished, or when any slot is finished
        while `pending > 0` admissible requests wait (so the host can
        refill the slot instead of idling it).  All of steps_cap /
        pending / start_pos / eos_id are traced scalars — only `chunk`
        (the buffer width) is static, so occupancy churn never retraces.

        With no EOS hits and no vacancies this body performs exactly the
        ops of `_fused_decode_fn`'s fori body in the same order — the
        differential identity test pins that bit-for-bit.
        """
        b = tok.shape[0]
        out0 = jnp.full((b, chunk), -1, jnp.int32)
        emitted0 = jnp.zeros((b,), jnp.int32)

        def cond(carry):
            i, _tok, _cache, _out, fin, _em = carry
            refill = jnp.any(fin) & (pending > 0)
            return (i < steps_cap) & ~jnp.all(fin) & ~refill

        def body(carry):
            i, tok, cache, out, fin, em = carry
            write = jnp.where(fin, jnp.int32(-1), tok)
            out = jax.lax.dynamic_update_slice(out, write[:, None], (0, i))
            em = em + jnp.where(fin, 0, 1).astype(jnp.int32)
            hit_eos = (eos_id >= 0) & (tok == eos_id) & ~fin
            fin = fin | hit_eos | (em >= remaining)
            logits, cache = self.bundle.decode_step(
                params, tok, cache, start_pos + i, attn_mask=mask)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (i + 1, tok, cache, out, fin, em)

        init = (jnp.asarray(0, jnp.int32), tok, cache, out0, finished,
                emitted0)
        steps, tok, cache, out, finished, emitted = jax.lax.while_loop(
            cond, body, init)
        return steps, tok, cache, out, finished, emitted

    def _admit_fn(self, params, toks, mask, cache, slot, offset):
        """Prefill one request at global offset and scatter it into `slot`.

        toks/mask: [1, Lb] left-padded prompt; slot/offset: traced int32
        scalars (no retrace across slots or clock values — one trace per
        prompt bucket Lb).  A fresh zero cache row is prefilled at
        positions [offset, offset + Lb) and written over the retired
        tenant's row with `dynamic_update_slice_in_dim` — required for
        ring (sliding-window) caches, whose admission path rolls a
        zeroed row into ring order (see models/common.py).  Returns
        (first greedy token scalar, updated pool cache).
        """
        row = self.bundle.init_cache(1, self.max_seq_len)
        logits, row = self.bundle.prefill(params, toks, row,
                                          attn_mask=mask, pos_offset=offset)

        def scatter(pool_leaf, row_leaf):
            # Batched leaves carry batch at axis 1 ([layers, B, ...]);
            # anything else (scalar bookkeeping leaves) passes through.
            if (getattr(pool_leaf, "ndim", 0) >= 2
                    and getattr(row_leaf, "ndim", -1) == pool_leaf.ndim
                    and row_leaf.shape[0] == pool_leaf.shape[0]
                    and row_leaf.shape[1] == 1
                    and row_leaf.shape[2:] == pool_leaf.shape[2:]):
                return jax.lax.dynamic_update_slice_in_dim(
                    pool_leaf, row_leaf.astype(pool_leaf.dtype), slot,
                    axis=1)
            return pool_leaf

        new_cache = jax.tree.map(scatter, cache, row)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
        return tok, new_cache

    # -- shape management --------------------------------------------------

    def _bucket_len(self, n: int) -> int:
        bkt = self.prompt_bucket
        return ((n + bkt - 1) // bkt) * bkt

    def _pad_batch(self, prompts: List[np.ndarray],
                   ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Left-pad to the bucketed max length.
        Returns (tokens [B, L], pad mask [B, L] (True = real), L)."""
        b = len(prompts)
        plen = self._bucket_len(max(len(p) for p in prompts))
        out = np.full((b, plen), self.pad_id, np.int32)
        mask = np.zeros((b, plen), bool)
        for i, p in enumerate(prompts):
            out[i, plen - len(p):] = p       # left padding
            mask[i, plen - len(p):] = True
        return out, mask, plen

    def _cache_for(self, batch: int):
        cache = self._cache_pool.get(batch)
        if cache is None:
            cache = self.bundle.init_cache(batch, self.max_seq_len)
            self._cache_pool[batch] = cache
        return cache

    @property
    def compile_counts(self) -> Dict[str, int]:
        """Jit-cache entry counts per engine entry point (plus the cache
        pool size) — the retrace regression tests assert these stay flat
        across repeated pulls at the same (batch, bucket)."""
        return {"prefill": self._prefill._cache_size(),
                "decode_loop": self._decode._cache_size(),
                "decode_fused": self._fused_decode._cache_size(),
                "decode_continuous": self._fused_continuous._cache_size(),
                "admit": self._admit._cache_size(),
                "cache_pool": len(self._cache_pool)}

    # -- generation --------------------------------------------------------

    def _validate(self, prompts: List[np.ndarray], max_new_tokens: int,
                  ) -> None:
        if not prompts:
            raise ValueError("generate() needs at least one prompt")
        if any(len(p) == 0 for p in prompts):
            raise ValueError("generate() got an empty prompt")
        if len(prompts) > self.max_batch:
            raise ValueError(
                f"batch of {len(prompts)} prompts exceeds max_batch="
                f"{self.max_batch}")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        plen = self._bucket_len(max(len(p) for p in prompts))
        if plen + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"bucketed prompt length {plen} + max_new_tokens "
                f"{max_new_tokens} exceeds max_seq_len={self.max_seq_len} "
                f"(the KV cache would overrun)")

    def generate(self, prompts: List[np.ndarray], max_new_tokens: int,
                 ) -> Tuple[np.ndarray, EngineStats]:
        """Greedy-decode `max_new_tokens` for each prompt.
        Returns (tokens [B, max_new_tokens], stats)."""
        self._validate(prompts, max_new_tokens)
        toks, mask, prompt_len = self._pad_batch(prompts)
        b = toks.shape[0]
        cache = self._cache_for(b)

        with obslog.span("engine.prefill", batch=b,
                         prompt_len=prompt_len) as sp:
            logits, cache = self._prefill(self.params, jnp.asarray(toks),
                                          cache, jnp.asarray(mask))
            logits.block_until_ready()
        t_prefill = sp.t1 - sp.t0

        # Decode-time pad mask over global positions: prompt pads stay
        # invalid, every decode-written slot (>= prompt_len) is valid.
        dec_mask = np.ones((b, self.max_seq_len), bool)
        dec_mask[:, :prompt_len] = mask

        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        with obslog.span("engine.decode", batch=b,
                         tokens=b * max_new_tokens,
                         decode_impl=self.decode_impl) as sp:
            if self.decode_impl == "fused":
                out_dev = self._fused_decode(
                    self.params, tok, cache, jnp.asarray(dec_mask),
                    jnp.asarray(prompt_len, jnp.int32), max_new_tokens)
                out = np.asarray(out_dev)       # the one host sync
            else:
                dmask = jnp.asarray(dec_mask)
                out = np.zeros((b, max_new_tokens), np.int32)
                for i in range(max_new_tokens):
                    out[:, i] = np.asarray(tok)
                    logits, cache = self._decode(
                        self.params, tok, cache,
                        jnp.asarray(prompt_len + i, jnp.int32), dmask)
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                tok.block_until_ready()
        t_decode = sp.t1 - sp.t0

        st = EngineStats(prefill_s=t_prefill, decode_s=t_decode,
                         tokens_out=b * max_new_tokens,
                         decode_impl=self.decode_impl)
        return out, st

    # -- continuous generation ---------------------------------------------

    def generate_continuous(self, requests: Iterable[EngineRequest], *,
                            n_slots: Optional[int] = None,
                            eos_id: Optional[int] = None,
                            chunk: int = 16,
                            step_time_s: Optional[float] = None,
                            time_scale: float = 1.0,
                            ) -> Tuple[Dict[int, np.ndarray], ContinuousStats]:
        """Serve `requests` with continuous (slot-level) batching.

        Decoding runs on a persistent pool of `n_slots` slots sharing one
        global KV clock; a request that hits `eos_id` or its own
        `max_new_tokens` retires mid-run and its slot is refilled from
        the queue (admission = single-row prefill at the clock offset —
        see `_admit_fn`).  When every slot drains the clock reseeds at
        zero with a fresh left-padded batch, which also recovers the
        arena near `max_seq_len`.

        The simulation clock orders arrivals (`EngineRequest.arrival_s`)
        against service: it advances by measured wall time × `time_scale`
        (DVFS factor), or deterministically by `step_time_s` per decode
        step / per prefill call when given (benchmarks assert on the
        resulting model time, independent of host noise).

        Returns ``({rid: tokens [n_i]}, ContinuousStats)`` — per-request
        streams are ragged (EOS-terminated streams include the EOS
        token).
        """
        reqs = list(requests)
        if not reqs:
            raise ValueError("generate_continuous() needs at least one "
                             "request")
        if len({r.rid for r in reqs}) != len(reqs):
            raise ValueError("generate_continuous() got duplicate request "
                             "ids")
        if eos_id is not None and eos_id < 0:
            raise ValueError(f"eos_id must be None or >= 0, got {eos_id}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        if self.bundle.family == "encdec":
            raise ValueError(
                "continuous batching is unsupported for the encdec family "
                "(absolute sinusoidal positions forbid offset admission; "
                "see models/encdec.py)")
        b = n_slots if n_slots is not None else min(self.max_batch,
                                                    len(reqs))
        if not 1 <= b <= self.max_batch:
            raise ValueError(f"n_slots={b} outside [1, max_batch="
                             f"{self.max_batch}]")
        sched = SlotScheduler(b, self.max_seq_len, self.prompt_bucket)
        for r in reqs:
            sched.validate_request(r)
        queue = RequestQueue(reqs)
        eos = jnp.asarray(-1 if eos_id is None else int(eos_id), jnp.int32)

        sim = 0.0
        prefill_s = decode_s = 0.0
        decode_steps = 0
        prefill_calls = 0
        chunks = blocked = drain = 0
        phases = obslog.PhaseTimes()
        outputs: Dict[int, np.ndarray] = {}
        t_call = obslog.CLOCK()

        def tick(wall_dt: float, units: int) -> None:
            nonlocal sim
            sim += (step_time_s * units if step_time_s is not None
                    else wall_dt * time_scale)

        def retired(rec: RequestRecord, now: float) -> None:
            """A retired request: its wall finish, output and span.  The
            span starts when the request was due: its `arrival_s`, or its
            admission if earlier (the engine's clock skips idle time)."""
            rec.finish_wall_s = now - t_call
            outputs[rec.rid] = np.asarray(rec.tokens, np.int32)
            if obslog.active():
                due = min(rec.arrival_s, rec.admit_wall_s)
                first = rec.first_token_wall_s
                obslog.record_span(
                    "engine.request", t_call + due, now, rid=rec.rid,
                    slot=rec.slot, tokens=rec.n_tokens,
                    prompt_len=rec.prompt_len,
                    queue_wait_s=rec.admit_wall_s - due,
                    ttft_s=None if first is None else first - due,
                    cancelled=rec.cancelled)

        # Per-slot device/host state between chunks.  Vacant slots carry
        # finished=True, remaining=0 and an all-True mask row (an
        # all-invalid attention window would produce NaN attention).
        cache = None
        tok = None
        valid = np.ones((b, self.max_seq_len), bool)
        finished = np.ones((b,), bool)
        remaining = np.zeros((b,), np.int32)

        while len(queue) or sched.any_live():
            with obslog.span("engine.bookkeep", acc=phases):
                # Deadline processing (request cancellation,
                # repro.faults): expired pending requests are abandoned
                # before admission; live slots past their deadline retire
                # mid-generate with the tokens emitted so far and free for
                # refill.  Deadlines are only checked between chunks, so
                # cancellation latency is bounded by one scheduler
                # iteration (admission prefills plus a chunk of decode).
                for req in queue.expired(sim):
                    queue.pop(req)
                    rec = sched.abandon(req, sim)
                    rec.finish_wall_s = obslog.CLOCK() - t_call
                    outputs[req.rid] = np.zeros((0,), np.int32)
                    if obslog.active():
                        obslog.emit("fault.request", rid=req.rid,
                                    action="abandon",
                                    deadline_s=req.deadline_s,
                                    queue_wait_s=rec.queue_wait_s)
                for slot in sched.due_cancellations(sim):
                    rec = sched.cancel(slot, sim)
                    finished[slot] = True
                    remaining[slot] = 0
                    if obslog.active():
                        obslog.emit("fault.request", rid=rec.rid,
                                    action="cancel", slot=slot,
                                    tokens=rec.n_tokens)
                    retired(rec, obslog.CLOCK())
                group = None
                if not sched.any_live():
                    arrived = queue.arrived(sim)
                    if not arrived:
                        sim = queue.next_arrival()  # idle: jump to arrival
                        continue
                    # Reseed: fresh left-padded batch at clock zero (same
                    # path as static generate — self._prefill at offset 0).
                    group = sched.seed_group(arrived)
                    plen = max(self._bucket_len(len(r.prompt))
                               for r in group)
                    toks = np.full((b, plen), self.pad_id, np.int32)
                    mask = np.zeros((b, plen), bool)
                    mask[len(group):, :] = True  # dummy rows: defined attn
                    for i, r in enumerate(group):
                        toks[i, plen - len(r.prompt):] = r.prompt
                        mask[i, plen - len(r.prompt):] = True
            if group is not None:
                with obslog.span("engine.reseed", acc=phases,
                                 rows=len(group), bucket=plen) as sp:
                    logits, cache = self._prefill(self.params,
                                                  jnp.asarray(toks),
                                                  self._cache_for(b),
                                                  jnp.asarray(mask))
                    logits.block_until_ready()
                with obslog.span("engine.bookkeep", acc=phases):
                    dt = sp.t1 - sp.t0
                    prefill_s += dt
                    prefill_calls += 1
                    tick(dt, 1)
                    for r in group:
                        queue.pop(r)
                    sched.seed(group, plen, sim)
                    for slot in range(len(group)):
                        sched.record_at(slot).admit_wall_s = sp.t0 - t_call
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    valid = np.ones((b, self.max_seq_len), bool)
                    valid[:, :plen] = mask
                    finished = np.ones((b,), bool)
                    finished[:len(group)] = False
                    remaining = np.zeros((b,), np.int32)
                    for i, r in enumerate(group):
                        remaining[i] = r.max_new_tokens
                # Run the admit loop before decoding: a request that
                # arrived during the seed prefill may already be
                # admissible into a vacant slot, and the fused loop
                # early-exits (steps=0) if it sees it pending instead.
                continue

            # Refill free slots from the arrived, admissible queue.
            while sched.free_slots():
                with obslog.span("engine.bookkeep", acc=phases):
                    cand = next((r for r in queue.arrived(sim)
                                 if sched.can_admit(r)), None)
                    if cand is not None:
                        lb = self._bucket_len(len(cand.prompt))
                        offset = sched.pos - lb
                        toks1 = np.full((1, lb), self.pad_id, np.int32)
                        mask1 = np.zeros((1, lb), bool)
                        toks1[0, lb - len(cand.prompt):] = cand.prompt
                        mask1[0, lb - len(cand.prompt):] = True
                        slot_guess = sched.free_slots()[0]
                if cand is None:
                    break
                with obslog.span("engine.admit", acc=phases, rid=cand.rid,
                                 bucket=lb, slot=slot_guess) as sp:
                    tok1, cache = self._admit(
                        self.params, jnp.asarray(toks1), jnp.asarray(mask1),
                        cache, jnp.asarray(slot_guess, jnp.int32),
                        jnp.asarray(offset, jnp.int32))
                    tok1.block_until_ready()
                    dt = obslog.CLOCK() - sp.t0
                    prefill_s += dt
                    prefill_calls += 1
                    tick(dt, 1)
                    slot = sched.admit(cand, sim)
                    assert slot == slot_guess
                    sched.record_at(slot).admit_wall_s = sp.t0 - t_call
                    queue.pop(cand)
                    tok = tok.at[slot].set(tok1)
                    row = np.zeros((self.max_seq_len,), bool)
                    row[offset + (lb - len(cand.prompt)):] = True
                    valid[slot] = row
                    finished[slot] = False
                    remaining[slot] = cand.max_new_tokens

            # One chunk of fused decode.  A live slot always has
            # remaining <= max_seq_len - pos (admission geometry), so
            # steps_cap >= 1 and the loop makes progress.  A slot still
            # vacant here waits on the KV clock if a request has arrived
            # (every admissible one was admitted above), else on the
            # queue's drain.
            with obslog.span("engine.bookkeep", acc=phases):
                live = sched.live_slots()
                steps_cap = min(chunk, self.max_seq_len - sched.pos)
                arrived = queue.arrived(sim)
                pending = sum(1 for r in arrived if sched.can_admit(r))
                vacant = b - len(live)
            with obslog.span("engine.chunk.upload", acc=phases,
                             live=len(live)) as up:
                steps_d, tok, cache, out_d, fin_d, em_d = \
                    self._fused_continuous(
                        self.params, tok, cache, jnp.asarray(valid),
                        jnp.asarray(sched.pos, jnp.int32),
                        jnp.asarray(finished), jnp.asarray(remaining), eos,
                        jnp.asarray(steps_cap, jnp.int32),
                        jnp.asarray(pending, jnp.int32), chunk)
            # The chunk's three spans tile the interval `decode_s` times.
            with obslog.span("engine.chunk.wait", acc=phases,
                             start=up.t1) as wt:
                steps = int(steps_d)             # the per-chunk host sync
            with obslog.span("engine.chunk.fetch", acc=phases, start=wt.t1,
                             steps=steps) as fe:
                out = np.asarray(out_d)
                fin_new = np.array(fin_d)        # copy: mutated on admit
                em = np.asarray(em_d)
            with obslog.span("engine.bookkeep", acc=phases):
                dt = fe.t1 - up.t0
                decode_s += dt
                decode_steps += steps
                chunks += 1
                tick(dt, steps)
                if steps == 0:
                    raise RuntimeError(
                        "continuous decode made no progress (scheduler "
                        "invariant violated)")
                if arrived:
                    blocked += vacant * steps
                else:
                    drain += vacant * steps
                for slot in live:
                    if em[slot]:
                        rec = sched.record_at(slot)
                        if rec.first_token_wall_s is None:
                            rec.first_token_wall_s = fe.t1 - t_call
                        sched.note_emitted(slot, out[slot, :em[slot]])
                sched.advance(steps, len(live))
                finished = fin_new
                remaining = remaining - em
                for slot in live:
                    if fin_new[slot]:
                        retired(sched.retire(slot, sim), fe.t1)

        recs = sched.records
        st = ContinuousStats(
            prefill_s=prefill_s, decode_s=decode_s,
            tokens_out=int(sum(r.n_tokens for r in recs)),
            decode_impl="fused", sim_s=sim, decode_steps=decode_steps,
            prefill_calls=prefill_calls, n_requests=len(recs),
            n_cancelled=sum(1 for r in recs if r.cancelled),
            mean_occupancy=sched.mean_occupancy,
            mean_queue_wait_s=(float(np.mean([r.queue_wait_s
                                              for r in recs]))
                               if recs else 0.0),
            records=recs, phase_s=phases.s, phase_n=phases.n,
            chunks=chunks, empty_slot_steps_blocked=blocked,
            empty_slot_steps_drain=drain)
        return outputs, st


class EngineEnvironment(BaseEnvironment):
    """Camel Environment backed by the real engine: pulling an arm serves
    one batch of synthetic prompts at that batch size and converts measured
    wall time into an `Observation`.

    Power comes from a pluggable `repro.obs` sensor (`sensor=` accepts a
    `PowerSensor` or a spec string like ``"replay:trace.jsonl"``): each
    pull is wrapped in an `EnergyMeter.measure()` window sampling the
    sensor at `sample_hz`.  The default (`sensor=None`) evaluates the
    analytical board model directly — and the out-of-the-box
    ``"simulated"`` sensor wraps that same model, whose constant
    per-pull reading the meter integrates exactly, so both paths produce
    bit-identical observations (asserted in tests/test_obs.py).  On a
    Jetson/dGPU deployment pass ``"sysfs"`` / ``"nvml"`` to use measured
    rail power instead.  Registry name: "engine/<arch>".

    With ``scheduler="continuous"`` a pull serves `requests_per_pull`
    Poisson arrivals (rate = `arrival_rate`, ragged prompt and output
    lengths from `ArrivalProcess`) through `generate_continuous` with
    the batch arm as the slot-pool width — the batch-size arms become
    max-concurrency arms, and the Observation carries measured
    per-request latency / queue wait / goodput instead of the analytic
    queueing model."""

    def __init__(self, engine: InferenceEngine, board, work,
                 arrival_rate: float = 1.0, prompt_len: int = 32,
                 max_new_tokens: int = 16, seed: int = 0,
                 sensor=None, sample_hz: float = 20.0,
                 scheduler: str = "static",
                 requests_per_pull: Optional[int] = None,
                 eos_id: Optional[int] = None, chunk: int = 16,
                 faults=None):
        if scheduler not in ("static", "continuous"):
            raise ValueError(f"scheduler must be 'static' or 'continuous', "
                             f"got {scheduler!r}")
        self.engine = engine
        self.board = board
        self.work = work
        self.platform = DVFSPlatform(board)
        self.arrival_rate = require_positive_rate(arrival_rate)
        self.prompt_len = prompt_len
        self.max_new_tokens = max_new_tokens
        self.scheduler = scheduler
        self.requests_per_pull = requests_per_pull
        self.eos_id = eos_id
        self.chunk = chunk
        self.seed_base = seed
        self.rng = np.random.default_rng(seed)
        # A zero FaultPlan is dropped outright so the default path stays
        # bit-identical (asserted in benchmarks/resilience.py).
        self.faults = faults if faults is not None \
            and not faults.is_zero else None
        self.sensor = make_sensor(sensor, platform=self.platform) \
            if sensor is not None else None
        if self.faults is not None and self.sensor is not None:
            from repro.faults import wrap_sensor
            self.sensor = wrap_sensor(self.sensor, self.faults)
        self.meter = EnergyMeter(self.sensor, hz=sample_hz) \
            if self.sensor is not None else None

    def _continuous_workload(self, round_index: int,
                             ) -> List[EngineRequest]:
        """Poisson arrivals with ragged prompt/output lengths, clipped so
        every request fits the engine arena (bucketed prompt +
        max_new_tokens <= max_seq_len)."""
        eng = self.engine
        vocab = eng.bundle.cfg.vocab_size
        n = self.requests_per_pull or 16
        ap = ArrivalProcess(interval_s=1.0 / self.arrival_rate,
                            kind="poisson",
                            prompt_median=self.prompt_len,
                            prompt_max=eng.max_seq_len,
                            max_new_tokens=self.max_new_tokens,
                            seed=self.seed_base + 7919 * (round_index + 1))
        reqs = []
        for r in ap.generate(n):
            mnt = int(self.rng.integers(1, self.max_new_tokens + 1))
            mnt = min(mnt, eng.max_seq_len - eng.prompt_bucket)
            lcap = ((eng.max_seq_len - mnt) // eng.prompt_bucket) \
                * eng.prompt_bucket
            plen = int(np.clip(r.prompt_len, 1, lcap))
            toks = self.rng.integers(1, vocab, size=plen).astype(np.int32)
            reqs.append(EngineRequest(rid=r.rid, prompt=toks,
                                      max_new_tokens=mnt,
                                      arrival_s=r.arrival_s))
        if self.faults is not None:
            from repro.faults import apply_request_faults
            reqs = apply_request_faults(reqs, self.faults)
        return reqs

    def _pull_continuous(self, batch: int, level: int,
                         round_index: int) -> Observation:
        util = self.work.utilization(batch)
        reqs = self._continuous_workload(round_index)
        factor = self.work.freq_factor(self.board, level) \
            / self.work.freq_factor(self.board, self.board.n_levels - 1)
        m = None
        kw = dict(n_slots=batch, eos_id=self.eos_id, chunk=self.chunk,
                  time_scale=factor)
        if self.meter is not None:
            set_util = getattr(self.sensor, "set_utilization", None)
            if set_util is not None:
                set_util(util)
            with self.meter.measure() as m:
                _, st = self.engine.generate_continuous(reqs, **kw)
        else:
            _, st = self.engine.generate_continuous(reqs, **kw)

        t_model = st.total_s * factor
        p = self.board.power(level, util) if m is None else m.avg_watts
        joules = p * t_model
        attribute_energy(st.records, joules)
        lat = float(np.mean([r.latency_s for r in st.records]))
        metadata = {"backend": "engine", "scheduler": "continuous",
                    "prefill_s": st.prefill_s, "decode_s": st.decode_s,
                    "decode_impl": st.decode_impl,
                    "tokens_per_s": st.tokens_per_s,
                    "goodput_rps": st.goodput_rps,
                    "n_requests": st.n_requests,
                    "n_cancelled": st.n_cancelled,
                    "decode_steps": st.decode_steps,
                    "mean_occupancy": st.mean_occupancy,
                    "mean_queue_wait_s": st.mean_queue_wait_s}
        if m is not None:
            metadata.update(sensor=m.sensor_name,
                            sensor_joules=m.joules,
                            sensor_peak_w=m.peak_watts,
                            sensor_samples=m.n_samples)
        # Latency/queue-wait are measured on the simulation clock (DVFS-
        # scaled service against real arrival gaps) — no analytic
        # queueing model, so construct the Observation directly.
        return Observation(energy=joules / max(st.n_requests, 1),
                           latency=lat, batch_time=t_model,
                           queue_wait=st.mean_queue_wait_s, backlog=0.0,
                           power=p, batch=batch, tokens=st.tokens_out,
                           metadata=metadata)

    def pull(self, knobs: Dict, round_index: int) -> Observation:
        batch = int(knobs["batch"])
        level = self.platform.level_of(knobs["freq_mhz"])
        self.platform.set_level(level)
        if self.scheduler == "continuous":
            return self._pull_continuous(batch, level, round_index)
        util = self.work.utilization(batch)
        vocab = self.engine.bundle.cfg.vocab_size
        prompts = [self.rng.integers(1, vocab, size=self.prompt_len)
                   .astype(np.int32) for _ in range(batch)]
        m = None
        if self.meter is not None:
            set_util = getattr(self.sensor, "set_utilization", None)
            if set_util is not None:
                set_util(util)
            with self.meter.measure() as m:
                _, st = self.engine.generate(prompts, self.max_new_tokens)
        else:
            _, st = self.engine.generate(prompts, self.max_new_tokens)

        # Frequency scaling of measured time (CPU measures f_max behavior):
        factor = self.work.freq_factor(self.board, level) \
            / self.work.freq_factor(self.board, self.board.n_levels - 1)
        t_batch = st.total_s * factor
        p = self.board.power(level, util) if m is None else m.avg_watts
        metadata = {"backend": "engine", "prefill_s": st.prefill_s,
                    "decode_s": st.decode_s,
                    "decode_impl": st.decode_impl,
                    "tokens_per_s": st.tokens_per_s}
        if m is not None:
            metadata.update(sensor=m.sensor_name,
                            sensor_joules=m.joules,
                            sensor_peak_w=m.peak_watts,
                            sensor_samples=m.n_samples)
        # Single-batch horizon (n_requests = batch): no saturation backlog —
        # a live pull measures one batch, it cannot observe queue growth.
        return observe(p, t_batch, batch, self.arrival_rate,
                       n_requests=batch, tokens=st.tokens_out,
                       metadata=metadata)
