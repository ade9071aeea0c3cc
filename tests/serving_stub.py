"""A JAX-free stand-in for ``ContinuousScheduler`` driving the HTTP tests.

The front door is duck-typed over the scheduler (``submit`` /
``run_segment`` / ``has_work`` / ``queue`` / ``slots`` / ``stats`` /
``engine.cfg``), so the HTTP conformance suite runs against this stub with
no model compile: real ``Request`` handles, a real ``BlockAllocator`` (so
block-reclaim assertions are exact), real ``TenantPolicy`` integration,
deterministic token emission (token *i* of a request is a pure function of
its prompt), and a tunable per-segment delay to make heartbeat/backpressure
timing testable.

Not collected by pytest (no ``test_`` prefix) — imported by
``test_serve_http.py``.
"""
from __future__ import annotations

import collections
import time
from types import SimpleNamespace

import numpy as np

from repro.configs.base import reduced_config
from repro.serve.policy import Overloaded, RateLimited
from repro.serve.request import (CANCELLED, EXPIRED, FINISHED, RUNNING,
                                 Request, SubmitRequest)
from repro.serve.scheduler import BlockAllocator


def stub_token(prompt, i: int, vocab: int = 997) -> int:
    """Deterministic token *i* for a prompt — the oracle shared by the
    stub and its tests."""
    return int((int(prompt[0]) * 7 + int(prompt[-1]) * 3 + 13 * i) % vocab)


class StubScheduler:
    """Continuous-scheduler lookalike: admit → emit ``steps_per_segment``
    tokens per live slot per segment → retire, with cancel/expiry sweeps at
    segment boundaries and full-budget block allocation, mirroring the real
    scheduler's observable contract."""

    def __init__(self, n_slots: int = 4, n_blocks: int = 32,
                 block_len: int = 8, max_len: int = 128,
                 steps_per_segment: int = 4, segment_delay_s: float = 0.0,
                 eos_id: int | None = None, policy=None,
                 clock=time.monotonic):
        self.n_slots = n_slots
        self.block_len = block_len
        self.max_len = max_len
        self.steps = steps_per_segment
        self.segment_delay_s = segment_delay_s
        self.eos_id = eos_id
        self.policy = policy
        self.clock = clock
        # the model the front door prices energy for (nothing runs it)
        self.engine = SimpleNamespace(cfg=reduced_config("tinyllama-1.1b"))
        self.spec_k = 0
        self.allocator = BlockAllocator(n_blocks)
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: list[Request | None] = [None] * n_slots
        self._next_rid = 0
        # segment counter value at each cancel-retire, for the
        # "blocks reclaimed within one segment" assertions
        self.last_cancel_segment: int | None = None
        self.stats = {
            "segments": 0, "admitted": 0, "retired": 0,
            "cancelled": 0, "expired": 0,
            "blocks_reclaimed_cancel": 0,
            "tenant_tokens": {},
            # the useful-token counters the energy report reads
            "prefill_tokens_real": 0, "slot_steps_live": 0,
            "spec_steps": 0, "spec_emitted": 0,
        }

    # -- submit ------------------------------------------------------------

    def submit(self, sub: SubmitRequest) -> Request:
        p = np.asarray(sub.prompt, np.int32).reshape(-1)
        if p.size < 1:
            raise ValueError("empty prompt")
        if sub.max_new_tokens is None or sub.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{sub.max_new_tokens}")
        if p.size + sub.max_new_tokens > self.max_len:
            raise ValueError("exceeds max_len")
        if self._blocks_for(p.size, sub.max_new_tokens) > self.allocator.capacity:
            raise ValueError("request larger than the block pool")
        tenant = sub.tenant if sub.tenant is not None else "default"
        ttft = sub.ttft_deadline_s
        if self.policy is not None:
            spec = self.policy.spec_for(tenant)
            priority = (sub.priority if sub.priority is not None
                        else spec.default_priority)
            cls = self.policy.class_for(priority)
            if ttft is None:
                ttft = cls.ttft_deadline_s
            # brownout shed mirrors the real scheduler: checked before the
            # rate gate so a shed never consumes bucket credit
            if self.policy.should_shed(priority):
                raise Overloaded(tenant, self.policy.shed_retry_after(),
                                 priority, self.policy.brownout_level)
            retry = self.policy.charge_rate(tenant, self.clock())
            if retry is not None:
                raise RateLimited(tenant, retry)
            self.policy.note_submitted(tenant)
        else:
            priority = sub.priority if sub.priority is not None else "standard"
        req = Request(rid=self._next_rid, prompt=p,
                      max_new_tokens=sub.max_new_tokens,
                      on_token=sub.on_token, submit_t=self.clock(),
                      ttft_deadline_s=ttft, deadline_s=sub.deadline_s,
                      tenant=tenant, priority=priority)
        self._next_rid += 1
        self.queue.append(req)
        return req

    # -- internals ---------------------------------------------------------

    def _blocks_for(self, prompt_len: int, max_new: int) -> int:
        return -(-(prompt_len + max_new) // self.block_len)

    def _emit(self, req: Request) -> None:
        tok = stub_token(req.prompt, len(req.tokens))
        if req.first_token_t is None:
            req.first_token_t = self.clock()
            if self.policy is not None:
                self.policy.observe_ttft(req.priority,
                                         req.first_token_t - req.submit_t)
        req._emit(tok)
        t = self.stats["tenant_tokens"]
        t[req.tenant] = t.get(req.tenant, 0) + 1
        if self.policy is not None:
            self.policy.note_tokens(req.tenant)

    def _retire(self, slot: int, state: str, reason: str) -> None:
        req = self.slots[slot]
        req.state = state
        req.finish_reason = reason
        req.finish_t = self.clock()
        if self.policy is not None and state == FINISHED:
            self.policy.observe_latency(req.priority,
                                        req.finish_t - req.submit_t)
        released = len(self.allocator.release(slot))
        self.slots[slot] = None
        self.stats["retired"] += 1
        if state == CANCELLED:
            self.stats["cancelled"] += 1
            self.stats["blocks_reclaimed_cancel"] += released
            self.last_cancel_segment = self.stats["segments"]
        elif state == EXPIRED:
            self.stats["expired"] += 1

    def _sweep(self) -> None:
        now = self.clock()
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            if req.cancel_requested:
                self._retire(slot, CANCELLED, "cancelled")
            elif req.deadline_s is not None and now - req.submit_t > req.deadline_s:
                self._retire(slot, EXPIRED, "expired")
        for req in [r for r in self.queue if r.cancel_requested]:
            self.queue.remove(req)
            req.state = CANCELLED
            req.finish_reason = "cancelled"
            req.finish_t = now
            self.stats["cancelled"] += 1
            self.last_cancel_segment = self.stats["segments"]

    def _admit(self) -> None:
        while self.queue:
            free = [s for s, r in enumerate(self.slots) if r is None]
            if not free:
                return
            req = (self.queue[0] if self.policy is None
                   else self.policy.select(self.queue))
            need = self._blocks_for(req.prompt_len, req.max_new_tokens)
            if not self.allocator.can_alloc(need):
                return  # defer the round, preserving order
            if self.policy is None:
                self.queue.popleft()
            else:
                self.policy.on_admitted(self.queue, req)
                self.queue.remove(req)
            slot = free[0]
            self.allocator.alloc(slot, need)
            req.slot_history.append(slot)
            req.state = RUNNING
            self.slots[slot] = req
            self.stats["admitted"] += 1
            self.stats["prefill_tokens_real"] += req.prompt_len
            self._emit(req)  # the prefill-sampled first token

    def run_segment(self) -> int:
        """One segment: sweep, admit, then up to ``steps_per_segment``
        emissions per live slot; retire at budget/eos."""
        if self.segment_delay_s:
            time.sleep(self.segment_delay_s)
        self.stats["segments"] += 1
        self._sweep()
        if self.policy is not None and self.policy.slo is not None:
            now = self.clock()
            target = self.policy.slo.cfg.target_class
            waiting = [now - r.submit_t for r in self.queue
                       if r.priority == target and r.first_token_t is None]
            self.policy.update_slo(waiting)
        self._admit()
        emitted = 0
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            for _ in range(self.steps):
                if len(req.tokens) >= req.max_new_tokens:
                    break
                self._emit(req)
                self.stats["slot_steps_live"] += 1
                emitted += 1
                if self.eos_id is not None and req.tokens[-1] == self.eos_id:
                    break
            if (self.eos_id is not None and req.tokens
                    and req.tokens[-1] == self.eos_id):
                self._retire(slot, FINISHED, "stop")
            elif len(req.tokens) >= req.max_new_tokens:
                self._retire(slot, FINISHED, "length")
        self._sweep()  # honor cancels that landed during the segment
        return emitted

    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slots)

    def run(self) -> None:
        while self.has_work():
            self.run_segment()


def drain_offline(sched, subs):
    """Offline-path oracle: submit everything up front, run to empty,
    return each request's tokens in submission order."""
    handles = [sched.submit(s) for s in subs]
    sched.run()
    return [list(h.tokens) for h in handles]
