"""Batched serving engine: continuous batching over a shared KV cache.

Slot-based decode (vLLM-lite): a fixed pool of `max_batch` slots, each with
its own cursor into the shared (L, B, S, Hkv, Dh) cache; requests join free
slots, decode steps run the whole pool, finished sequences free their slot.
The decode step is the same jitted `decode_step` the dry-run lowers.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    def __init__(self, model_mod, params, cfg, max_batch: int = 8,
                 max_seq: int = 512, temperature: float = 0.0):
        self.mod = model_mod
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.temperature = temperature
        self.cache = model_mod.init_cache(cfg, max_batch, max_seq)
        self.pos = np.zeros(max_batch, dtype=np.int32)
        self.slot_req: list = [None] * max_batch
        self.queue: list = []
        self._step = jax.jit(
            lambda p, c, t, q: model_mod.decode_step(p, c, t, q, cfg))

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self) -> None:
        for slot in range(self.max_batch):
            if self.slot_req[slot] is None and self.queue:
                req = self.queue.pop(0)
                self.slot_req[slot] = req
                self.pos[slot] = 0
                # prefill the prompt token-by-token through decode (simple,
                # exact; bulk prefill uses forward_with_cache)
                for tok in req.prompt[:-1]:
                    self._advance_slot(slot, tok)
                req._next = req.prompt[-1]

    def _advance_slot(self, slot: int, token: int) -> int:
        tokens = np.zeros(self.max_batch, dtype=np.int32)
        tokens[slot] = token
        # a copy of pos: the step is dispatched asynchronously and may alias
        # a host buffer on the CPU, and pos is mutated right after dispatch
        logits, self.cache = self._step(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(self.pos.copy()))
        self.pos[slot] += 1
        return int(jnp.argmax(logits[slot]))

    def step(self) -> int:
        """One engine iteration over every active slot; returns #active."""
        self._admit()
        active = [s for s in range(self.max_batch)
                  if self.slot_req[s] is not None]
        if not active:
            return 0
        tokens = np.zeros(self.max_batch, dtype=np.int32)
        for s in active:
            tokens[s] = self.slot_req[s]._next
        logits, self.cache = self._step(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(self.pos.copy()))
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        for s in active:
            req = self.slot_req[s]
            self.pos[s] += 1
            req.out.append(int(nxt[s]))
            req._next = int(nxt[s])
            if len(req.out) >= req.max_new or self.pos[s] >= self.max_seq - 1:
                req.done = True
                self.slot_req[s] = None
        return len(active)

    def run(self) -> None:
        while self.queue or any(r is not None for r in self.slot_req):
            self.step()
