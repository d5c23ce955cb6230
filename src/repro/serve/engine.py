"""Batched serving engine: slot-based continuous batching over a fixed cache.

The engine owns a cache pytree for ``max_batch`` sequence *slots* of
``max_len`` tokens (KV cache / MLA latent cache / SSM state per the model
family) plus per-slot cursors.  Requests are prefilled one at a time
(bucketed prompt lengths for the attention families to bound recompiles;
exact lengths for SSM/hybrid, whose state integrates every position) and
inserted into a free slot; ``step()`` then decodes one token for *every*
active slot in a single batched ``forward_decode`` — the batching the
decode_32k shape cell measures.

All device work happens in three jit'd programs: ``lm_prefill`` (one
prompt; the LM head at its last position only), ``lm_insert`` (the
prompt's cache rows written into its slot) and ``lm_decode`` (every slot one
token; greedy tokens picked on the device).  The insert and, unsharded, the
decode donate the cache, so it is updated where it lies and exists once.
The Python layer only does slot bookkeeping.

Spans (``repro.core.tracing``, ``mafia.lm.*``) mark the prefill, the insert
and each decode step with its dispatch, fetch and sampling; the same clock
readings are kept as records (``prefill_records``, ``decode_records``,
rows of :data:`PREFILL_FIELDS` / :data:`DECODE_FIELDS`), beside the
counters (``counters``) and the routed choices per expert and MoE layer
(``expert_counts``), which the jitted programs return with their outputs.
A request submitted with ``capture=n`` keeps the logits its first token and
its first ``n`` decoded tokens were sampled from (``Request.logits``).

The engine reports through the shared :class:`repro.serve.metrics
.ServeMetrics` (same surface as the classical tiers): one ``record_batch``
per batched decode (active slots = occupancy, wall time around the forward
= device seconds, so ``served`` counts generated tokens — the decode tier's
unit of work) and one ``record_request`` per retirement (submit→finish
latency feeds p50/p99).

Admission runs through the shared
:class:`~repro.serve.scheduling.AdmissionQueue` (the classical async
tier's primitive): ``queue_limit`` turns overflow into ``QueueFull``
backpressure, and two SLO *classes* report separately — **prefill**
(time-to-first-token, deadline stamped at submit) via ``metrics_prefill``
and **decode** (full completion) via ``metrics_decode`` — while the
aggregate ``metrics`` surface stays exactly as before.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.models.transformer import (
    ModelConfig,
    forward_decode,
    forward_full,
    init_cache,
)
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduling import AdmissionQueue, SlotPool, bucket_for

__all__ = ["ServeEngine", "Request", "PREFILL_FIELDS", "DECODE_FIELDS"]

_SEQ_KEYS = ("k", "v", "ckv", "kr")       # cache leaves with a sequence axis
# one record per prefill: start (time.perf_counter), prompt length, bucket,
# seconds of the forward with the first token's fetch and sampling, seconds
# of the insert's dispatch
PREFILL_FIELDS = ("t0", "plen", "bucket", "prefill_s", "insert_s")
# one record per decode step: start, active slots, cache rows they attend
# over (sum of pos + 1), seconds of dispatch, fetch and sampling
DECODE_FIELDS = ("t0", "n_active", "ctx_rows", "forward_s", "fetch_s",
                 "sample_s")
_RECORDS = 1 << 16


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    tokens: list[int] = dataclasses.field(default_factory=list)
    slot: int | None = None
    t_submit: float = 0.0
    # prefill-class SLO deadline (absolute monotonic seconds): the instant
    # by which the first token must be sampled.  Also what the shared
    # AdmissionQueue's ``due``/``next_due_in`` bookkeeping reads.
    deadline: float | None = None
    t_first_token: float | None = None
    # decode steps whose logits are kept, beside the prefill's last row
    capture: int = 0
    logits: list = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new_tokens

    @property
    def capturing(self) -> bool:
        """Whether the next decoded token's logits are kept."""
        return 0 < len(self.logits) <= self.capture

    @property
    def ttft_s(self) -> float | None:
        """Submit → first-token latency (the prefill-class SLO unit)."""
        return (None if self.t_first_token is None
                else self.t_first_token - self.t_submit)


class ServeEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        *,
        max_batch: int = 8,
        max_len: int = 512,
        greedy: bool = True,
        seed: int = 0,
        mesh: Any | None = None,
        plan: Any | None = None,
        prefill_slo_s: float | None = None,
        decode_slo_s: float | None = None,
        queue_limit: int | None = None,
    ) -> None:
        """``mesh``/``plan`` (from :func:`repro.sharding.planner.plan_for`
        with ``mode="decode"``) turn the engine distributed: params live on
        the plan's shardings, the cache pytree on the plan's cache specs,
        and both jit'd step functions carry explicit in/out shardings — the
        same layout the decode_32k dry-run cells prove out.

        ``prefill_slo_s`` / ``decode_slo_s`` are the two token-tier SLO
        classes served off the shared :class:`AdmissionQueue` (the same
        primitive the classical async tier schedules against): the prefill
        class is time-to-first-token (submit → first sampled token — queue
        wait plus one prefill), the decode class is full completion
        (submit → last token).  Each class reports through its own
        :class:`ServeMetrics` (``metrics_prefill`` / ``metrics_decode``,
        with per-class ``slo_misses``); the aggregate ``metrics`` surface
        is unchanged.  ``queue_limit`` bounds admission — ``submit``
        raises :class:`~repro.serve.scheduling.QueueFull` beyond it, the
        same backpressure contract as the async classical tier."""
        self.cfg = cfg
        self.mesh = mesh
        self.plan = plan
        if mesh is not None and plan is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            ns = lambda tree: jax.tree.map(
                lambda s: None if s is None else NamedSharding(mesh, s), tree,
                is_leaf=lambda x: x is None or isinstance(x, P),
            )
            self._param_sh = ns(plan.param_specs)
            self._cache_sh = ns(plan.cache_specs) if plan.cache_specs else None
            params = jax.device_put(params, self._param_sh)
        else:
            self._param_sh = self._cache_sh = None
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.greedy = greedy
        self._key = jax.random.key(seed)
        self.caches = init_cache(cfg, max_batch, max_len)
        if self._cache_sh is not None:
            self.caches = jax.device_put(self.caches, self._cache_sh)
        self.pos = np.zeros(max_batch, np.int32)
        # slot occupancy lives in the shared SlotPool; ``active`` aliases
        # its flags array so the decode mask and the pool stay one state
        self.slots = SlotPool(max_batch)
        self.active = self.slots.flags
        self.last_token = np.zeros(max_batch, np.int32)
        self._slots: dict[int, Request] = {}
        self._next_rid = 0
        # shared scheduling primitive: same bounded FIFO + deadline
        # bookkeeping the classical async tier admits through
        self._queue = AdmissionQueue(queue_limit)
        self._finished: list[Request] = []
        self._exact_prefill = cfg.family in ("ssm", "hybrid")
        self.prefill_slo_s = prefill_slo_s
        self.decode_slo_s = decode_slo_s
        self.metrics = ServeMetrics()
        self.metrics_prefill = ServeMetrics()
        self.metrics_decode = ServeMetrics()
        self.counters = dict.fromkeys(
            ("decode_steps", "decoded_tokens", "prefill_tokens",
             "prefill_padded_tokens"), 0)
        self.expert_counts = (np.zeros((cfg.n_moe_layers, cfg.n_experts),
                                       np.int64)
                              if cfg.n_moe_layers else None)
        self.prefill_records: collections.deque = collections.deque(
            maxlen=_RECORDS)
        self.decode_records: collections.deque = collections.deque(
            maxlen=_RECORDS)

    # ------------------------------------------------------------- jit fns
    @functools.cached_property
    def _prefill(self):
        cfg = self.cfg

        def lm_prefill(params, tokens, plen):
            """(logits at the last prompt position (Vp,), caches, routed
            choices per expert of the prompt's tokens)."""
            real = jnp.arange(tokens.shape[1])[None, :] < plen
            logits, caches, _, counts = forward_full(
                params, cfg, tokens, return_cache=True,
                head_positions=(plen - 1)[None], count_mask=real)
            return logits[0], caches, counts
        return jax.jit(lm_prefill)

    @functools.cached_property
    def _decode(self):
        cfg = self.cfg

        def lm_decode(params, token, caches, pos, active):
            """(greedy tokens, logits, caches, routed choices per expert of
            the active slots)."""
            logits, caches, counts = forward_decode(
                params, cfg, token, caches, pos, count_mask=active)
            col = jnp.arange(logits.shape[-1])[None, :]
            masked = jnp.where(col < cfg.vocab_size, logits, -jnp.inf)
            return jnp.argmax(masked, axis=-1), logits, caches, counts

        if self._cache_sh is not None:
            return jax.jit(
                lm_decode,
                in_shardings=(self._param_sh, None, self._cache_sh, None,
                              None),
                out_shardings=(None, None, self._cache_sh, None),
                donate_argnums=(2,))
        return jax.jit(lm_decode, donate_argnums=(2,))

    @functools.cached_property
    def _put(self):
        def lm_insert(caches, new, slot):
            """The prompt's cache rows into ``slot``: a ring-buffer leaf
            (shorter than the prompt) keeps the last rows, each at its
            position modulo the ring."""
            def put(key, leaf, row):
                row = row.astype(leaf.dtype)
                if key in _SEQ_KEYS and row.shape[2] > leaf.shape[2]:
                    S, win = row.shape[2], leaf.shape[2]
                    idx = np.arange(S - win, S)
                    return leaf.at[:, slot, idx % win].set(row[:, 0, idx])
                start = (0, slot) + (0,) * (leaf.ndim - 2)
                return jax.lax.dynamic_update_slice(leaf, row, start)
            return {k: put(k, caches[k], new[k]) for k in caches}

        if self._cache_sh is not None:
            return jax.jit(lm_insert, out_shardings=self._cache_sh,
                           donate_argnums=(0,))
        return jax.jit(lm_insert, donate_argnums=(0,))

    @functools.cached_property
    def _row(self):
        return jax.jit(lambda logits, i: logits[i])

    # --------------------------------------------------------- bookkeeping
    def submit(self, prompt: list[int], max_new_tokens: int = 16,
               capture: int = 0) -> int:
        """Queue a request; returns its id.  ``capture`` keeps the logits of
        its first token and of its first ``capture`` decoded tokens."""
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) >= self.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} >= engine max_len {self.max_len}")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        now = time.monotonic()
        req = Request(self._next_rid, prompt, max_new_tokens, t_submit=now,
                      deadline=(None if self.prefill_slo_s is None
                                else now + self.prefill_slo_s),
                      capture=capture)
        self._queue.push(req)      # QueueFull propagates as backpressure
        self._next_rid += 1
        return req.rid

    def _free_slots(self) -> list[int]:
        return self.slots.free()

    def _bucket(self, n: int) -> int:
        if self._exact_prefill:
            return n
        return bucket_for(n, self.max_len, floor=8)

    def _sample(self, logits) -> int:
        lf = np.array(logits, np.float32)        # writable copy
        lf[self.cfg.vocab_size:] = -np.inf       # mask vocab padding
        if self.greedy:
            return int(lf.argmax())
        self._key, sub = jax.random.split(self._key)
        return int(jax.random.categorical(sub, jnp.asarray(lf)))

    # -------------------------------------------------------------- prefill
    def _insert(self, req: Request, slot: int) -> None:
        prompt = np.asarray(req.prompt, np.int32)
        plen = len(prompt)
        if plen >= self.max_len:  # submit() validates; keep a -O-proof guard
            raise ValueError(
                f"prompt length {plen} >= engine max_len {self.max_len}")
        sp = self._bucket(plen)
        padded = np.zeros(sp, np.int32)
        padded[:plen] = prompt
        t0 = time.perf_counter()
        with tracing.span(tracing.LM_PREFILL, plen=plen, bucket=sp):
            logits, pcache, counts = self._prefill(
                self.params, jnp.asarray(padded)[None, :], np.int32(plen))
            lf, counts = jax.device_get((logits, counts))
            first = self._sample(lf)
        t1 = time.perf_counter()
        with tracing.span(tracing.LM_INSERT):
            self.caches = self._put(self.caches, pcache, np.int32(slot))
        t2 = time.perf_counter()
        self.prefill_records.append((t0, plen, sp, t1 - t0, t2 - t1))
        self.counters["prefill_tokens"] += plen
        self.counters["prefill_padded_tokens"] += sp - plen
        if counts is not None:
            self.expert_counts += counts
        if req.capture:
            req.logits.append(np.asarray(lf, np.float32))
        self.pos[slot] = plen
        self.slots.acquire(slot)
        self.last_token[slot] = first
        req.slot = slot
        req.tokens.append(first)
        self._slots[slot] = req
        # prefill SLO class: the first token was just sampled — TTFT is
        # queue wait + this prefill, judged against the admission deadline
        req.t_first_token = time.monotonic()
        self.metrics_prefill.record_request(
            req.ttft_s, t_submit=req.t_submit, t_done=req.t_first_token,
            missed_slo=(req.deadline is not None
                        and req.t_first_token > req.deadline))

    def _retire(self, slot: int, req: Request) -> None:
        now = time.monotonic()
        self.slots.release(slot)
        self._finished.append(req)
        del self._slots[slot]
        latency = now - req.t_submit
        self.metrics.record_request(latency,
                                    t_submit=req.t_submit, t_done=now)
        # decode SLO class: full completion (submit → last token)
        self.metrics_decode.record_request(
            latency, t_submit=req.t_submit, t_done=now,
            missed_slo=(self.decode_slo_s is not None
                        and latency > self.decode_slo_s))

    # ----------------------------------------------------------------- step
    def step(self) -> dict[int, int]:
        """Admit queued requests into free slots, then decode one token for
        every active slot.  Returns {request id: new token}."""
        for slot in self._free_slots():
            if not self._queue:
                break
            (req,) = self._queue.take(1)
            self._insert(req, slot)
        # Retire requests already satisfied by prefill (max_new_tokens=1:
        # _insert sampled their one token) *before* decoding — the decode
        # loop skips done requests, so without this sweep their slots never
        # free and run_to_completion spins to max_steps.
        for slot, req in list(self._slots.items()):
            if req.done:
                self._retire(slot, req)
        if not self.slots.any_active:
            return {}

        n_active = len(self._slots)
        ctx_rows = int(self.pos[self.active].sum()) + n_active
        with tracing.span(tracing.LM_DECODE, n_active=n_active):
            t0 = time.perf_counter()
            with tracing.span(tracing.LM_DECODE_FORWARD):
                tok, logits, self.caches, counts = self._decode(
                    self.params, jnp.asarray(self.last_token), self.caches,
                    jnp.asarray(self.pos), jnp.asarray(self.active))
            t1 = time.perf_counter()
            with tracing.span(tracing.LM_DECODE_FETCH):
                # one wait for the step; rows leave the device only where
                # they are needed: every row to sample from them here, the
                # captured rows beside the device's greedy tokens
                if self.greedy:
                    tok, counts = jax.device_get((tok, counts))
                    rows = {s: np.asarray(self._row(logits, np.int32(s)))
                            for s, r in self._slots.items() if r.capturing}
                else:
                    lg, counts = jax.device_get((logits, counts))
                    rows = {s: lg[s] for s in self._slots}
            t2 = time.perf_counter()
            # the window around dispatch and fetch is honest device time for
            # this decode batch: the fetch waited for its result
            self.metrics.record_batch(n_active, t2 - t0)
            with tracing.span(tracing.LM_DECODE_SAMPLE):
                out: dict[int, int] = {}
                for slot, req in list(self._slots.items()):
                    row = rows.get(slot)
                    if self.greedy:
                        tok_s = int(tok[slot])
                    else:       # masks padding, samples categorically
                        tok_s = self._sample(row)
                    if req.capturing:
                        req.logits.append(np.asarray(row, np.float32))
                    req.tokens.append(tok_s)
                    out[req.rid] = tok_s
                    self.last_token[slot] = tok_s
                    self.pos[slot] += 1
                    if req.done or self.pos[slot] >= self.max_len - 1:
                        self._retire(slot, req)
            t3 = time.perf_counter()
        self.decode_records.append((t0, n_active, ctx_rows, t1 - t0, t2 - t1,
                                    t3 - t2))
        self.counters["decode_steps"] += 1
        self.counters["decoded_tokens"] += n_active
        if counts is not None:
            self.expert_counts += counts
        return out

    def take_finished(self) -> list[Request]:
        """The requests retired since the last call, in retirement order."""
        done, self._finished = self._finished, []
        return done

    # ------------------------------------------------------------ driver
    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        steps = 0
        while (self._queue or self._slots) and steps < max_steps:
            self.step()
            steps += 1
        return sorted(self._finished, key=lambda r: r.rid)
