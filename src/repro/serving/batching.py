"""Continuous batching: slot-based scheduler over a shared decode cache.

The decode cache is a fixed [L, B_slots, S, ...] tree; requests are
assigned to free slots on arrival, prefilled individually (batch-1 prefill
against the same cache length), scattered into their slot, and then decoded
together with every other active slot in a single decode step per token.
Finished slots (EOS or token budget) are freed immediately, so the batch
composition changes every step - the vLLM-style iteration-level scheduling
that Dandelion's "cold start per request is fine" philosophy matches: a new
request never waits for the current batch to drain.
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
        self.cur_tokens = np.zeros((num_slots,), np.int32)
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
        self._steps = 0
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
            self.params, self.cache, jnp.asarray(self.cur_tokens)).compile()
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
                with tracing.span("batcher.sync"):
                    first = int(jnp.argmax(logits[0]))
                with tracing.span("batcher.insert_slot"):
                    self.cache = insert_slot(self.cache, one_cache, slot, self.batch_axes)
                tracing.count("batcher.prefills")
                tracing.count("batcher.prefill_tokens", len(prompt))
                tracing.count("batcher.prefill_padded_tokens", self.cache_len)
                self.slots[slot] = req
                req.generated.append(first)
                self.cur_tokens[slot] = first
                self._maybe_finish(slot)

    def _maybe_finish(self, slot: int):
        req = self.slots[slot]
        if req is None:
            return
        if len(req.generated) >= req.max_new_tokens or (
            req.eos_id >= 0 and req.generated and req.generated[-1] == req.eos_id
        ):
            req.done = True
            self.slots[slot] = None

    # ------------------------------------------------------------------
    def step(self) -> List[Tuple[int, int]]:
        """Admit waiting requests, run one decode step, emit (rid, token)."""
        self._admit()
        rids = [r.rid for r in self.slots if r is not None]
        if not rids:
            return []
        with tracing.span("batcher.step", rids=rids):
            with tracing.span("batcher.decode"):
                tokens = jnp.asarray(self.cur_tokens)
                logits, self.cache = self.decode_step(self.params, self.cache, tokens)
            # the sync's end is when each listed request's token reached the host
            with tracing.span("batcher.sync"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            tracing.count("batcher.decode_steps")
            tracing.count("batcher.decode_slot_steps", len(rids))
            out = []
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                tok = int(nxt[i])
                req.generated.append(tok)
                self.cur_tokens[i] = tok
                out.append((req.rid, tok))
                self._maybe_finish(i)
            self._steps += 1
            return out

    def run_to_completion(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        for _ in range(max_steps):
            if not self.waiting and self.active == 0:
                break
            self.step()
        return {req.rid: req.generated for req in self.all_requests}
