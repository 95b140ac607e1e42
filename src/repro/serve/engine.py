"""Serving engines: static batching and continuous batching over KV caches.

Two engines share the uniform model API:

* :class:`ServeEngine` — the classic static batch: requests are grouped into
  fixed-size, same-prompt-length batches and decoded in lock step; the batch
  retires when every stream finishes.  This is the serving analogue of the
  *classic exchange operator* the paper critiques: a fixed assignment of
  work to workers, so one long sequence holds every slot hostage.
* :class:`ContinuousEngine` — the paper's fix, applied to decode slots
  instead of relational partitions: parallelism (the fixed decode batch
  shape) is decoupled from the assignment of requests to slots.  A
  :class:`SlotAllocator` keeps a slot map over ONE shared KV cache;
  finished sequences are evicted between decode steps and freed slots are
  refilled from a pending queue (prefill-on-admit scatters the new cache
  rows in place — no retrace, no flush of the running batch).

The continuous decode keeps a fixed ``[batch_size, 1]`` shape with per-slot
positions (``ModelApi.decode_step_slots``), so XLA compiles exactly two
programs (prefill per prompt-length bucket, one decode step) no matter how
requests arrive and finish.  With every slot at the same position the slot
decode is bit-identical to the static step — ``tests/test_serve.py`` holds
the two engines to the same greedy outputs.

Expert-parallel models route the decode step's token dispatch through the
communication multiplexer: when a mesh context is active and
``cfg.moe_impl == "ep_shardmap"``, the engine builds an auto-tuned
:class:`~repro.core.multiplexer.CommMultiplexer` for the *decode-shaped*
message sizes (:func:`repro.core.autotune.decode_table_stats` — tiny
per-step buffers, so the tuner collapses to unchunked transport) and the
MoE layer ships its per-expert capacity buffers through it, under the same
tuned schedules as the relational exchanges.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import registry
from repro.obs.trace import maybe_span


def sample_token(key, logits: jax.Array, temperature: float = 0.0) -> jax.Array:
    """Greedy (t=0) or temperature sampling; logits [B, vocab] -> [B]."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class Request:
    prompt: np.ndarray  # int32 [prompt_len]
    max_new_tokens: int
    eos_id: int = -1  # -1: never stops early
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    # --- continuous batching: arrival + per-request stats -------------------
    arrival_step: int = 0          # decode-step tick at which it may be admitted
    admitted_step: int | None = None
    finished_step: int | None = None
    ttft_s: float | None = None    # wall from ARRIVAL to first token
    decode_tok_s: float | None = None  # tokens/s over the decode phase
    _t_arrive: float | None = dataclasses.field(default=None, repr=False)
    _t_first: float | None = dataclasses.field(default=None, repr=False)

    @property
    def num_new_tokens(self) -> int:
        return len(self.out_tokens)


class SlotAllocator:
    """Slot map over the shared KV cache: admission + eviction-on-finish.

    The paper's flexible exchange in miniature — the fixed resource (decode
    slots = cache rows) is decoupled from the work assigned to it.  Holds
    the invariant ``free + live == num_slots`` at every step boundary
    (``check()``); a leaked slot is a leaked cache row.
    """

    def __init__(self, num_slots: int):
        self.num_slots = num_slots
        self._free: list[int] = list(range(num_slots - 1, -1, -1))  # pop() -> slot 0 first
        self.live: dict[int, Request] = {}

    @property
    def num_free(self) -> int:
        return len(self._free)

    def admit(self, request: Request) -> int:
        """Assign a free slot to ``request``; caller prefills the cache row."""
        if not self._free:
            raise RuntimeError("no free slot (caller must check num_free)")
        slot = self._free.pop()
        self.live[slot] = request
        return slot

    def release(self, slot: int) -> Request:
        """Eviction-on-finish: the slot returns to the free list immediately."""
        request = self.live.pop(slot)
        self._free.append(slot)
        return request

    def check(self) -> None:
        assert len(self._free) + len(self.live) == self.num_slots, (
            f"slot leak: free={len(self._free)} live={len(self.live)} "
            f"!= {self.num_slots}"
        )
        assert set(self._free).isdisjoint(self.live), (self._free, self.live)


class ServeEngine:
    """Greedy/temperature STATIC batched generation over the uniform model API."""

    def __init__(self, api: registry.ModelApi, batch_size: int, capacity: int,
                 temperature: float = 0.0, seed: int = 0):
        self.api = api
        self.cfg = api.cfg
        self.batch_size = batch_size
        self.capacity = capacity
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        self._prefill = jax.jit(api.prefill)
        self._decode = jax.jit(api.decode_step)
        self.stats = {"prefill_tokens": 0, "decode_steps": 0, "slot_steps": 0,
                      "wall": 0.0}

    def _prefill_batch(self, params, prompts: np.ndarray, extra: dict | None = None):
        batch = {"tokens": jnp.asarray(prompts)}
        if extra:
            batch.update({k: jnp.asarray(v) for k, v in extra.items()})
        logits, cache = self._prefill(params, batch)
        self.stats["prefill_tokens"] += int(prompts.size)
        return logits, cache

    def generate(
        self,
        params,
        requests: list[Request],
        extra_inputs: dict | None = None,
    ) -> list[Request]:
        """Run one static batch of same-length prompts to completion."""
        t0 = time.perf_counter()
        assert len(requests) <= self.batch_size
        plen = requests[0].prompt.shape[0]
        assert all(r.prompt.shape[0] == plen for r in requests), "bucket by length"
        B = self.batch_size
        prompts = np.zeros((B, plen), np.int32)
        for i, r in enumerate(requests):
            prompts[i] = r.prompt

        logits, cache = self._prefill_batch(params, prompts, extra_inputs)
        # Decode continues after the WHOLE prefill context — for VLM that is
        # patches + prompt rows, not just the prompt — into a capacity-length
        # cache (pad if needed).
        ctx_len = int(jax.tree.leaves(cache)[0].shape[2])
        cache = self._grow_cache(cache, ctx_len)

        max_new = max(r.max_new_tokens for r in requests)
        # Split BEFORE the first sample: reusing self.key both directly and
        # as the parent of later splits correlated the first token of every
        # batch (and max_new==1 batches never advanced the key at all).
        self.key, sub = jax.random.split(self.key)
        tokens = sample_token(sub, logits, self.temperature)
        live = np.array([not r.done for r in requests] + [False] * (B - len(requests)))
        for i, r in enumerate(requests):
            r.out_tokens.append(int(tokens[i]))
            if r.max_new_tokens <= 1 or int(tokens[i]) == r.eos_id:
                r.done = True
                live[i] = False

        pos = ctx_len
        for step in range(1, max_new):
            if pos >= self.capacity or not live.any():
                break
            self.key, sub = jax.random.split(self.key)
            logits, cache = self._decode(params, tokens[:, None], cache, jnp.int32(pos))
            tokens = sample_token(sub, logits, self.temperature)
            self.stats["decode_steps"] += 1
            self.stats["slot_steps"] += B
            pos += 1
            arr = np.asarray(tokens)
            for i, r in enumerate(requests):
                if live[i]:
                    r.out_tokens.append(int(arr[i]))
                    if len(r.out_tokens) >= r.max_new_tokens or arr[i] == r.eos_id:
                        r.done = True
                        live[i] = False
        for r in requests:
            r.done = True
        self.stats["wall"] += time.perf_counter() - t0
        return requests

    def _grow_cache(self, cache: Any, filled: int) -> Any:
        """Pad prefill-length cache arrays out to ``self.capacity`` slots.

        Identifies the cache-sequence dim as the one equal to ``filled``
        in the reference (capacity-sized) cache template.
        """
        template = jax.eval_shape(lambda: self.api.init_cache(self.batch_size, self.capacity))

        def grow(leaf, ref):
            if leaf.shape == ref.shape:
                return leaf
            pads = []
            for have, want in zip(leaf.shape, ref.shape):
                assert want >= have, (leaf.shape, ref.shape)
                pads.append((0, want - have))
            return jnp.pad(leaf, pads)

        return jax.tree.map(grow, cache, template)


def generate_bucketed(
    engine: ServeEngine, params, requests: list[Request],
    extra_inputs: dict | None = None,
) -> list[Request]:
    """Static-batch a MIXED-length workload: bucket by prompt length, then
    run fixed batches per bucket — the baseline the continuous engine beats.
    Requests are served in arrival order within each bucket."""
    buckets: dict[int, list[Request]] = {}
    for r in requests:
        buckets.setdefault(r.prompt.shape[0], []).append(r)
    for plen in sorted(buckets):
        group = buckets[plen]
        for i in range(0, len(group), engine.batch_size):
            engine.generate(params, group[i : i + engine.batch_size], extra_inputs)
    return requests


def make_mixed_workload(
    vocab_size: int,
    num_requests: int,
    prompt_lens: Sequence[int],
    max_new: int,
    rng: np.random.Generator,
    arrival_rate: float = 0.0,
) -> list[Request]:
    """The standard mixed workload the CLI and the bench both run.

    Prompt lengths cycle through ``prompt_lens`` (one prefill bucket each),
    output budgets are uniform in ``[1, max_new]``, and with
    ``arrival_rate`` r > 0 request ``i`` arrives at decode step ``i / r``
    (0 = everything queued up front).
    """
    reqs = []
    for i in range(num_requests):
        plen = prompt_lens[i % len(prompt_lens)]
        reqs.append(Request(
            prompt=rng.integers(0, vocab_size, plen, dtype=np.int32),
            max_new_tokens=int(rng.integers(1, max_new + 1)),
            arrival_step=int(i / arrival_rate) if arrival_rate > 0 else 0,
        ))
    return reqs


def engine_record(reqs: list[Request], stats: dict, wall: float) -> dict:
    """One engine run -> the comparable summary record (bench JSON / CLI)."""
    total_new = sum(len(r.out_tokens) for r in reqs)
    rec = {
        "requests": len(reqs),
        "new_tokens": total_new,
        "decode_steps": stats["decode_steps"],
        "slot_steps": stats["slot_steps"],
        "wall_s": round(wall, 4),
        "tok_s": round(total_new / wall, 2) if wall > 0 else None,
    }
    ttfts = [r.ttft_s for r in reqs if r.ttft_s is not None]
    if ttfts:
        rec["ttft_mean_s"] = round(float(np.mean(ttfts)), 4)
        rec["ttft_p99_s"] = round(float(np.quantile(ttfts, 0.99)), 4)
    if "live_slot_steps" in stats:
        rec["live_slot_steps"] = stats["live_slot_steps"]
    return rec


# ----------------------------------------------------------------------------
# Continuous batching.
# ----------------------------------------------------------------------------

class ContinuousEngine:
    """Continuous-batching generation: slot map + admission between steps.

    One persistent ``[batch_size, capacity]`` KV cache; requests stream
    through it.  Per iteration:

    1. **admit** — free slots are refilled from the pending queue (grouped
       by prompt length, one batched prefill per group, scattered into the
       slots' cache regions in place);
    2. **decode** — one fixed-shape ``decode_step_slots`` over ALL slots at
       their own positions (dead slots compute masked garbage);
    3. **evict** — streams that hit ``max_new_tokens``/EOS/capacity release
       their slot immediately, so the next iteration can admit into it.

    Stats are per-request (``ttft_s``, ``decode_tok_s``) plus engine
    aggregates; ``slot_steps`` (= decode_steps x batch_size) is the
    slot-occupancy currency the static-vs-continuous comparison uses.
    """

    def __init__(self, api: registry.ModelApi, batch_size: int, capacity: int,
                 temperature: float = 0.0, seed: int = 0, tracer=None):
        #: Optional :class:`repro.obs.trace.Tracer` — admission rounds,
        #: prefill groups and decode steps become spans on it.
        self.tracer = tracer
        if api.decode_step_slots is None:
            raise NotImplementedError(
                f"continuous batching needs a per-position KV cache; "
                f"family {api.cfg.family!r} does not provide decode_step_slots"
            )
        self.api = api
        self.cfg = api.cfg
        self.batch_size = batch_size
        self.capacity = capacity
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        self._prefill = jax.jit(api.prefill)
        self._decode = jax.jit(api.decode_step_slots)
        self._scatter = jax.jit(self._scatter_prefill)
        self.alloc = SlotAllocator(batch_size)
        self.stats = {
            "prefill_tokens": 0, "decode_steps": 0, "slot_steps": 0,
            "live_slot_steps": 0, "idle_steps": 0, "admitted": 0,
            "finished": 0, "wall": 0.0,
        }
        self.mux = self._make_decode_multiplexer()

    # -- EP dispatch over the communication multiplexer ---------------------

    def _make_decode_multiplexer(self):
        """Auto-tune a multiplexer for the decode step's expert traffic.

        Only when the model is expert-parallel (``ep_shardmap``) and a mesh
        context is active; the tuner prices the per-step ``E x C`` capacity
        buffers (tiny), so it lands on the unchunked scheduled transport.
        """
        if self.cfg.moe_impl != "ep_shardmap":
            return None
        from repro.distributed.sharding import current_mesh_context

        ctx = current_mesh_context()
        if ctx is None:
            return None
        # A parallel unit is one device of the JOINT (pod, exchange) axis:
        # on a pod mesh the dispatch runs the two-level fabric across
        # pods * exchange_size units, and the tuner must price the capacity
        # buffers the MoE layer actually sizes for that unit count.
        pods = ctx.mesh.shape[ctx.pod_axis] if ctx.pod_axis is not None else 1
        units = ctx.exchange_size * pods
        if units <= 1:
            return None
        from repro.core.autotune import decode_table_stats
        from repro.core.multiplexer import make_multiplexer

        stats = decode_table_stats(self.cfg, self.batch_size, units)
        return make_multiplexer(ctx.mesh, auto=True, table_stats=[stats])

    def _mux_scope(self):
        if self.mux is None:
            return contextlib.nullcontext()
        from repro.core.multiplexer import use_multiplexer

        return use_multiplexer(self.mux)

    # -- cache scatter (prefill-on-admit) -----------------------------------

    @staticmethod
    def _scatter_prefill(cache, pref, slots, active):
        """Write prefilled cache rows into their slots' regions, in place.

        ``slots [B]`` is a PERMUTATION of the slot ids: row ``j`` of the
        prefill batch lands in slot ``slots[j]`` when ``active[j]``;
        inactive rows re-write their target slot's current bytes (a no-op)
        so every slot is written exactly once — deterministic scatter, and
        the jitted program is reused for any number of admits (the admit
        count only changes ``active``'s values, not any shape).
        """
        def upd(leaf, p):
            # leaf [L, B, capacity, ...]; p [L, B, plen, ...]
            plen = p.shape[2]
            cur = jnp.take(leaf, slots, axis=1)[:, :, :plen]
            mask = active.reshape((1, -1) + (1,) * (leaf.ndim - 2))
            val = jnp.where(mask, p.astype(leaf.dtype), cur)
            return leaf.at[:, slots, :plen].set(val)

        return jax.tree.map(upd, cache, pref)

    def _admit_group(self, params, cache, requests: list[Request], step: int,
                     t0: float, extra: dict | None):
        """Prefill one same-prompt-length group and scatter it into slots."""
        B, plen = self.batch_size, requests[0].prompt.shape[0]
        prompts = np.zeros((B, plen), np.int32)
        for j, r in enumerate(requests):
            prompts[j] = r.prompt
        batch = {"tokens": jnp.asarray(prompts)}
        if extra:
            batch.update({k: jnp.asarray(v) for k, v in extra.items()})
        with maybe_span(self.tracer, "repro.prefill", "serve", len=plen,
                        requests=len(requests), step=step):
            logits, pref_cache = self._prefill(params, batch)
            jax.block_until_ready(logits)
        self.stats["prefill_tokens"] += len(requests) * plen
        # The context a slot starts with is the PREFILL CACHE length, not the
        # prompt length — the VLM frontend prepends patch rows, so its cache
        # is patches + prompt wide.  Decode continues after the whole prefix.
        ctx_len = int(jax.tree.leaves(pref_cache)[0].shape[2])
        if ctx_len >= self.capacity:
            raise ValueError(
                f"admission rejected: prefill context of {ctx_len} rows "
                f"(prompt {plen} + side inputs) cannot fit a capacity-"
                f"{self.capacity} cache slot"
            )

        slot_of = [self.alloc.admit(r) for r in requests]
        # complete the slot vector to a permutation of range(B): inactive
        # rows target the remaining slots and rewrite their current bytes
        rest = [s for s in range(B) if s not in set(slot_of)]
        slots = np.array(slot_of + rest[: B - len(slot_of)], np.int32)
        active = np.zeros((B,), bool)
        active[: len(requests)] = True
        cache = self._scatter(cache, pref_cache, jnp.asarray(slots),
                              jnp.asarray(active))

        self.key, sub = jax.random.split(self.key)
        first = np.asarray(sample_token(sub, logits, self.temperature))
        now = time.perf_counter() - t0
        for j, r in enumerate(requests):
            r.admitted_step = step
            r.out_tokens.append(int(first[j]))
            r.ttft_s = now - (r._t_arrive or 0.0)
            r._t_first = now
            self.stats["admitted"] += 1
            self._positions[slot_of[j]] = ctx_len
            self._tokens[slot_of[j]] = int(first[j])
            if r.max_new_tokens <= 1 or int(first[j]) == r.eos_id:
                self._finish(slot_of[j], r, step, t0)
        return cache

    def _finish(self, slot: int, r: Request, step: int, t0: float):
        r.done = True
        r.finished_step = step
        dt = (time.perf_counter() - t0) - (r._t_first or 0.0)
        if r.num_new_tokens > 1 and dt > 0:
            r.decode_tok_s = (r.num_new_tokens - 1) / dt
        self.stats["finished"] += 1
        self.alloc.release(slot)
        # park the dead slot at position 0 with token 0: it keeps decoding
        # (fixed batch shape) but its writes land in a region the next
        # admission's prefill scatter overwrites
        self._positions[slot] = 0
        self._tokens[slot] = 0

    # -- the serve loop -----------------------------------------------------

    def serve(
        self,
        params,
        requests: list[Request],
        extra_inputs: dict | None = None,
    ) -> list[Request]:
        """Run a mixed-length workload to completion with slot refill.

        Requests become admittable at ``arrival_step`` (a decode-step tick —
        virtual time, so tests and benches are deterministic).  Among the
        arrived, freed slots go to the LONGEST remaining budget first (LPT
        scheduling: starting a long sequence late is what stretches the
        makespan tail; ties keep arrival order, so uniform workloads admit
        FIFO).  Raises UP FRONT (before any state mutates) on requests that
        can never be admitted — prompt plus any side-input context rows
        (VLM patches) must fit a cache slot.
        """
        side = 0
        if extra_inputs and "patches" in extra_inputs:
            # the VLM frontend prepends this many rows to every slot's cache
            side = int(np.asarray(extra_inputs["patches"]).shape[1])
        for r in requests:
            if r.prompt.shape[0] + side >= self.capacity:
                raise ValueError(
                    f"admission rejected: prompt of {r.prompt.shape[0]} tokens"
                    + (f" + {side} side-input rows" if side else "")
                    + f" cannot fit a capacity-{self.capacity} cache slot"
                )
        t0 = time.perf_counter()
        B = self.batch_size
        pending = sorted(requests, key=lambda r: r.arrival_step)
        cache = self.api.init_cache(B, self.capacity)
        self._positions = np.zeros((B,), np.int32)
        self._tokens = np.zeros((B,), np.int32)
        step = 0

        with self._mux_scope():
            while pending or self.alloc.live:
                # -- admission: refill freed slots from the arrived queue --
                n_arrived = 0
                while (n_arrived < len(pending)
                       and pending[n_arrived].arrival_step <= step):
                    n_arrived += 1
                for i in range(n_arrived):  # TTFT clock starts at arrival
                    if pending[i]._t_arrive is None:
                        pending[i]._t_arrive = time.perf_counter() - t0
                admittable: list[Request] = []
                if n_arrived and self.alloc.num_free:
                    # LPT pick among the arrived; admit in arrival order
                    pick = sorted(
                        range(n_arrived),
                        key=lambda i: -pending[i].max_new_tokens,
                    )[: self.alloc.num_free]
                    chosen = set(pick)
                    admittable = [pending[i] for i in sorted(chosen)]
                    pending = [r for i, r in enumerate(pending)
                               if i not in chosen]
                by_len: dict[int, list[Request]] = {}
                for r in admittable:
                    by_len.setdefault(r.prompt.shape[0], []).append(r)
                if by_len:
                    with maybe_span(self.tracer, "repro.round", "serve",
                                    round=step, admitted=len(admittable),
                                    groups=len(by_len)):
                        for plen in sorted(by_len):
                            cache = self._admit_group(
                                params, cache, by_len[plen], step, t0,
                                extra_inputs,
                            )
                self.alloc.check()

                if not self.alloc.live:
                    # nothing to decode: idle tick toward the next arrival
                    step += 1
                    self.stats["idle_steps"] += 1
                    continue

                # -- one fixed-shape decode step over every slot -----------
                self.key, sub = jax.random.split(self.key)
                with maybe_span(self.tracer, "repro.decode", "serve",
                                step=step, live=len(self.alloc.live)):
                    logits, cache = self._decode(
                        params, jnp.asarray(self._tokens[:, None]), cache,
                        jnp.asarray(self._positions),
                    )
                    sampled = np.asarray(
                        sample_token(sub, logits, self.temperature)
                    )
                self.stats["decode_steps"] += 1
                self.stats["slot_steps"] += B
                self.stats["live_slot_steps"] += len(self.alloc.live)

                # -- bookkeeping + eviction-on-finish ----------------------
                for slot, r in list(self.alloc.live.items()):
                    tok = int(sampled[slot])
                    r.out_tokens.append(tok)
                    self._tokens[slot] = tok
                    self._positions[slot] += 1
                    if (r.num_new_tokens >= r.max_new_tokens
                            or tok == r.eos_id
                            or self._positions[slot] >= self.capacity):
                        self._finish(slot, r, step, t0)
                step += 1
                self.alloc.check()

        self.stats["wall"] += time.perf_counter() - t0
        return requests


__all__ = [
    "ServeEngine",
    "ContinuousEngine",
    "SlotAllocator",
    "Request",
    "sample_token",
    "generate_bucketed",
    "make_mixed_workload",
    "engine_record",
]
