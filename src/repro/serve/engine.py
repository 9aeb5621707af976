"""Serving engine: KV-cache pytrees, jitted prefill/decode steps, a batched
generate loop, and a request-queue driver (bucketed batching).

decode_step lowers ONE new token against a ``max_len`` KV cache — this is
the function the ``decode_32k`` / ``long_500k`` dry-run cells compile.

The submit/drain request-queue shape of :class:`BatchedServer` is reused
by the analysis service tier (:class:`repro.service.AnalysisServer`),
which drains queued analyze/sweep requests through a coalescing,
disk-cached :class:`repro.service.AnalysisService` instead of a token
generator.
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import time

import jax
import jax.numpy as jnp

from repro.models.common import materialize, shardings


def make_caches(model, batch: int, max_len: int, key=None):
    """Zero-init cache pytree mirroring the model's stage structure; cache
    entries default to the model's activation dtype."""
    recs = model.cache_recs(batch, max_len)
    return materialize(recs, jax.random.PRNGKey(0) if key is None else key,
                       default_dtype=jnp.dtype(model.cfg.act_dtype))


@dataclasses.dataclass
class Request:
    uid: int
    tokens: list[int]
    max_new: int = 16
    result: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


class Engine:
    """Static-batch generation engine with jitted prefill + decode.

    With a ``mesh`` (and the sharding ``rule`` the model's constraints
    use), params and caches are placed by the rule's ``PartitionSpec``s
    and every step runs under that mesh. Caches are donated to the step
    that updates them, so one copy of the KV cache is live at a time."""

    def __init__(self, model, params, max_len: int, rule=None, mesh=None):
        self.model, self.max_len = model, max_len
        self.rule, self.mesh = rule, mesh
        if mesh is not None:
            params = jax.device_put(
                params, shardings(model.param_recs(), mesh, rule))
        self.params = params

        def _prefill(params, batch, caches):
            return model.prefill(params, batch, caches, rule=rule)

        def _decode(params, caches, tokens, pos):
            return model.decode_step(params, caches, tokens, pos, rule=rule)

        self.prefill = jax.jit(_prefill, donate_argnums=2)
        self.decode = jax.jit(_decode, donate_argnums=1)

    def mesh_context(self):
        """The context the engine's steps run in: its mesh, if any."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def new_caches(self, batch: int):
        """Zero caches for ``batch`` rows, placed on the engine's mesh."""
        caches = make_caches(self.model, batch, self.max_len)
        if self.mesh is None:
            return caches
        recs = self.model.cache_recs(batch, self.max_len)
        return jax.device_put(caches, shardings(recs, self.mesh, self.rule))

    def _sample(self, logits, temperature: float, key):
        if temperature == 0.0:
            return jnp.argmax(logits[:, -1], axis=-1)[:, None]
        probs = jax.nn.softmax(logits[:, -1].astype(jnp.float32)
                               / temperature, axis=-1)
        return jax.random.categorical(
            key, jnp.log(probs + 1e-9), axis=-1)[:, None]

    def generate(self, tokens, n_new: int, temperature: float = 0.0,
                 key=None, extras: dict | None = None):
        """tokens: (b, s0) int32 prompt. Returns (b, n_new) generated ids."""
        b, s0 = tokens.shape
        assert s0 + n_new <= self.max_len, (s0, n_new, self.max_len)
        key = jax.random.PRNGKey(0) if key is None else key
        with self.mesh_context():
            caches = self.new_caches(b)
            batch = {"tokens": tokens, **(extras or {})}
            logits, caches = self.prefill(self.params, batch, caches)
            out = []
            tok = self._sample(logits, temperature, key)
            out.append(tok)
            for i in range(1, n_new):
                key, sub = jax.random.split(key)
                logits, caches = self.decode(self.params, caches, tok,
                                             jnp.int32(s0 + i - 1))
                tok = self._sample(logits, temperature, sub)
                out.append(tok)
        return jnp.concatenate(out, axis=1)


def left_pad(prompts: list[list[int]]):
    """(b, longest) int32 batch of ``prompts``, left-padded with token 0."""
    s_max = max(len(p) for p in prompts)
    return jnp.asarray([[0] * (s_max - len(p)) + p for p in prompts],
                       jnp.int32)


class BatchedServer:
    """Request-queue driver: buckets same-length prompts into fixed batch
    slots, pads short buckets, runs the Engine per bucket. A lightweight
    stand-in for continuous batching at the driver level."""

    def __init__(self, engine: Engine, batch_size: int = 4,
                 max_wait_s: float = 0.0):
        self.engine = engine
        self.batch_size = batch_size
        self.max_wait_s = max_wait_s
        self._queue: queue.Queue[Request] = queue.Queue()
        self._served: list[int] = []    # batch sizes actually used

    def submit(self, req: Request):
        self._queue.put(req)

    def drain(self) -> list[Request]:
        """Serve everything currently queued; returns completed requests."""
        done = []
        while not self._queue.empty():
            bucket: list[Request] = []
            t0 = time.perf_counter()
            while (len(bucket) < self.batch_size
                   and (not self._queue.empty()
                        or time.perf_counter() - t0 < self.max_wait_s)):
                try:
                    bucket.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            if not bucket:
                break
            n_new = max(r.max_new for r in bucket)
            gen = self.engine.generate(left_pad([r.tokens for r in bucket]),
                                       n_new)
            self._served.append(len(bucket))
            for i, r in enumerate(bucket):
                r.result = [int(t) for t in gen[i][:r.max_new]]
                r.done = True
                done.append(r)
        return done
