"""Continuous batching: slot-based scheduler over a shared decode cache.

The decode cache is a fixed [L, B_slots, S, ...] tree; requests are
assigned to free slots on arrival, prefilled individually (batch-1 prefill
against the same cache length), scattered into their slot, and then decoded
together with every other active slot in a single decode step per token.
Finished slots are freed at once, so the batch composition changes every
step - the vLLM-style iteration-level scheduling that Dandelion's "cold
start per request is fine" philosophy matches: a new request never waits
for the current batch to drain.

The next tokens never leave the device on the way to the next step: a
decode step's input is the device argmax of the step before it, and an
admitted request's first token is the argmax of its prefill, written into
its slot on the device. The host runs one step ahead. ``step`` queues
decode step n+1 and only then copies step n's tokens (and any first tokens
queued before step n+1) to the host, so the copy overlaps step n+1 on the
device and no queued step waits on the host. The budget is a count, so a
request that has all its tokens computed frees its slot before the next
step is queued, with no wasted step. An EOS is seen one step late: the
request's slot has already been computed once more by then, and that
token is discarded (at most one slot-step per request); a slot re-admitted
meanwhile is overwritten by ``insert_slot`` behind the step in flight.
Served tokens are those of a synchronous loop: the same greedy argmax of
the same programs.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.models.common import is_spec
from repro.models.model import ModelApi


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: int = -1
    arrival: float = 0.0
    # filled by the scheduler
    generated: List[int] = field(default_factory=list)
    done: bool = False


def _batch_axis_tree(api: ModelApi, batch: int, cache_len: int):
    """For each cache leaf, the index of its batch dim (from logical axes)."""
    spec = api.cache_spec(batch, cache_len)

    def ax(s):
        return s.axes.index("batch") if "batch" in s.axes else None

    return jax.tree_util.tree_map(ax, spec, is_leaf=is_spec)


def insert_slot(cache, one, slot: int, batch_axes):
    """Scatter a batch-1 cache tree into ``slot`` of the batched cache."""

    def put(c, o, bax):
        if bax is None:
            return c
        idx = [slice(None)] * c.ndim
        idx[bax] = slice(slot, slot + 1)
        return c.at[tuple(idx)].set(o.astype(c.dtype))

    return jax.tree_util.tree_map(put, cache, one, batch_axes)


@jax.jit
def _next_tokens(logits):
    """The greedy next token of every slot, left on the device."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@jax.jit
def _put_first(tokens, logits, slot):
    """``tokens`` with ``slot`` set to the greedy token of a batch-1
    prefill's ``logits``; ``slot`` is traced, so one program serves all."""
    return tokens.at[slot].set(jnp.argmax(logits[0], axis=-1).astype(jnp.int32))


class ContinuousBatcher:
    """Iteration-level scheduler. Host-side control, device-side steps."""

    def __init__(
        self,
        api: ModelApi,
        params,
        *,
        num_slots: int,
        cache_len: int,
        extras_fn=None,
    ):
        self.api = api
        self.params = params
        self.num_slots = num_slots
        self.cache_len = cache_len
        self.extras_fn = extras_fn  # rid -> dict of prefill extras
        self.cache = api.init_cache(num_slots, cache_len)
        self.batch_axes = _batch_axis_tree(api, num_slots, cache_len)
        self.slots: List[Optional[Request]] = [None] * num_slots
        # each slot's next decode input, on the device
        self.cur_tokens = jnp.zeros((num_slots,), jnp.int32)
        # tokens computed for each slot's request, counted when queued
        self._issued = [0] * num_slots
        # (tokens, [(slot, req)]) for each queued program, oldest first, whose
        # tokens are not on the host yet: tokens[slot] is req's next token
        self._inflight: List[Tuple[jax.Array, List[Tuple[int, Request]]]] = []
        self.waiting: List[Request] = []
        # jitted on first call, or compiled ahead of time by ``compile``;
        # named functions, so the programs are ``jit_prefill`` and
        # ``jit_decode_step`` in a profiler's trace

        def prefill(p, t, pl, **kw):
            return api.prefill(p, t, pl, **kw)

        def decode_step(p, c, t):
            return api.decode_step(p, c, t)

        self.prefill_step = jax.jit(prefill)
        self.decode_step = jax.jit(decode_step)
        self.all_requests: List[Request] = []

    def compile(self) -> Dict[str, float]:
        """Compile both steps ahead of time for this batcher's fixed shapes
        (batch-1 prefill at ``cache_len``, decode over every slot) and keep
        the executables. Returns the seconds each compile took."""
        kw = self.extras_fn(0) if self.extras_fn else {}
        tokens = jnp.zeros((1, self.cache_len), jnp.int32)
        plens = jnp.ones((1,), jnp.int32)
        t0 = time.perf_counter()  # det-lint: waive[wall-clock] reason=reports real compile time
        self.prefill_step = self.prefill_step.lower(
            self.params, tokens, plens, **kw).compile()
        t1 = time.perf_counter()  # det-lint: waive[wall-clock] reason=reports real compile time
        self.decode_step = self.decode_step.lower(
            self.params, self.cache, self.cur_tokens).compile()
        t2 = time.perf_counter()  # det-lint: waive[wall-clock] reason=reports real compile time
        return {"prefill": t1 - t0, "decode": t2 - t1}

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        self.waiting.append(req)
        self.all_requests.append(req)

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _admit(self):
        while self.waiting:
            slot = self._free_slot()
            if slot is None:
                return
            req = self.waiting.pop(0)
            prompt = req.prompt[: self.cache_len]
            pad = self.cache_len - len(prompt)
            with tracing.span("batcher.admit", rid=req.rid, slot=slot,
                              prompt_len=len(prompt)):
                with tracing.span("batcher.prefill"):
                    tokens = jnp.asarray([prompt + [0] * pad], jnp.int32)
                    plens = jnp.asarray([len(prompt)], jnp.int32)
                    kw = self.extras_fn(req.rid) if self.extras_fn else {}
                    logits, one_cache = self.prefill_step(self.params, tokens, plens, **kw)
                    self.cur_tokens = _put_first(self.cur_tokens, logits, np.int32(slot))
                with tracing.span("batcher.insert_slot"):
                    self.cache = insert_slot(self.cache, one_cache, slot, self.batch_axes)
                tracing.count("batcher.prefills")
                tracing.count("batcher.prefill_tokens", len(prompt))
                tracing.count("batcher.prefill_padded_tokens", self.cache_len)
                self.slots[slot] = req
                self._issued[slot] = 0
                self._queue(self.cur_tokens, [(slot, req)])

    def _queue(self, tokens, live: List[Tuple[int, Request]]):
        """Note a queued program that computes one token for each of
        ``live``; a slot whose request now has its budget's tokens is
        freed, as no later step needs it."""
        self._inflight.append((tokens, live))
        for slot, req in live:
            self._issued[slot] += 1
            if self._issued[slot] >= req.max_new_tokens:
                self.slots[slot] = None

    def _copy(self, n: int) -> List[Tuple[int, int]]:
        """Copy the oldest ``n`` in-flight token sets to the host, append
        them to their requests and return them as (rid, token) pairs. A
        token for a request that has already ended (its EOS was copied
        after the next step was queued) is discarded."""
        out = []
        for i, (tokens, reqs) in enumerate(self._inflight[:n]):
            # each later entry is a program queued after this one
            behind = i < len(self._inflight) - 1
            # the sync's end is when each listed request's token reached the host
            with tracing.span("batcher.sync", rids=[r.rid for _, r in reqs], behind=behind):
                host = np.asarray(tokens)
            tracing.count("batcher.syncs")
            if behind:
                tracing.count("batcher.syncs_behind")
            for slot, req in reqs:
                if req.done:
                    tracing.count("batcher.discarded_slot_steps")
                    continue
                tok = int(host[slot])
                req.generated.append(tok)
                out.append((req.rid, tok))
                if len(req.generated) >= req.max_new_tokens or (
                        req.eos_id >= 0 and tok == req.eos_id):
                    req.done = True
                    if self.slots[slot] is req:
                        self.slots[slot] = None
        del self._inflight[:n]
        return out

    # ------------------------------------------------------------------
    def step(self) -> List[Tuple[int, int]]:
        """Admit waiting requests and queue one decode step over the live
        slots, then copy to the host the tokens of every program queued
        before that step; with no live slot, copy all that is in flight.
        Returns the (rid, token) pairs that reached the host."""
        self._admit()
        ahead = len(self._inflight)
        live = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        if not live:
            return self._copy(ahead)
        with tracing.span("batcher.step", rids=[r.rid for _, r in live]):
            with tracing.span("batcher.decode"):
                logits, self.cache = self.decode_step(self.params, self.cache, self.cur_tokens)
                self.cur_tokens = _next_tokens(logits)
            self._queue(self.cur_tokens, live)
            tracing.count("batcher.decode_steps")
            tracing.count("batcher.decode_slot_steps", len(live))
            return self._copy(ahead)

    def run_to_completion(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        for _ in range(max_steps):
            if not self.waiting and self.active == 0 and not self._inflight:
                break
            self.step()
        return {req.rid: req.generated for req in self.all_requests}
