"""Serving engine: KV-cache pytrees, jitted prefill/decode steps, a batched
generate loop, and a request-queue driver (bucketed batching).

decode_step lowers ONE new token against a ``max_len`` KV cache — this is
the function the ``decode_32k`` / ``long_500k`` dry-run cells compile.

Generated tokens stay on the device until their batch ends: each serving
step samples inside its own program and writes the token into a
``(batch, max_len)`` int32 buffer that the next step takes, donated, and
:meth:`BatchedServer.drain` reads the whole buffer to the host in one
transfer per batch, with the held experts' counts of a model that has
held-expert layers (``LM.held_counts``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.common import constrain, materialize, shardings


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
    batch: int = -1             # the server's id of the batch that served it


class Engine:
    """Static-batch generation engine with jitted prefill + decode.

    With a ``mesh`` (and the sharding ``rule`` the model's constraints
    use), params and caches are placed by the rule's ``PartitionSpec``s
    and every step runs under that mesh. Caches are donated to the step
    that updates them, and the step writes only each layer's new rows into
    them (``LM._run_stages``): one copy of the KV cache is live, and the
    compiled step holds no second one, neither as scratch nor as a copy
    into or out of the layer loop."""

    def __init__(self, model, params, max_len: int, rule=None, mesh=None):
        self.model, self.max_len = model, max_len
        self.rule, self.mesh = rule, mesh
        if mesh is not None:
            params = jax.device_put(
                params, shardings(model.param_recs(), mesh, rule))
        self.params = params

        def _prefill(params, batch, caches):
            return model.prefill(params, batch, caches, rule=rule)

        def _decode_logits(params, caches, tokens, pos):
            return model.decode_step(params, caches, tokens, pos, rule=rule)

        def _first(logits, key, pos, temperature):
            tok = self._sample(logits, temperature, key).astype(jnp.int32)
            out = jnp.zeros((tok.shape[0], max_len), jnp.int32)
            if rule is not None:
                out = constrain(out, rule, ("batch", None))
            return tok, jax.lax.dynamic_update_slice(out, tok, (0, pos))

        def _decode(params, caches, tok, pos, key, out, temperature):
            key, sub = jax.random.split(key)
            logits, caches = model.decode_step(params, caches, tok, pos,
                                               rule=rule)
            # the sample reads the logits as ``_decode_logits`` returns
            # them: fused into the head's matmul, the argmax would pick
            # from unrounded sums, and the served tokens would differ from
            # the argmax of the replayed logits
            logits = jax.lax.optimization_barrier(logits)
            tok = self._sample(logits, temperature, sub).astype(jnp.int32)
            out = jax.lax.dynamic_update_slice(out, tok, (0, pos + 1))
            return caches, tok, key, out

        self.prefill = jax.jit(_prefill, donate_argnums=2)
        #: the prefill's logits -> (first token, token buffer holding it)
        self.first = jax.jit(_first, static_argnums=3)
        #: one serving step: decode, sample, write the token into the buffer
        self.decode = jax.jit(_decode, donate_argnums=(1, 5),
                              static_argnums=6)
        #: one decode step that returns its logits (``replay_logits``)
        self.decode_logits = jax.jit(_decode_logits, donate_argnums=1)
        #: (after prefill, after the last decode step) of the last
        #: generate call: the held experts' routed pairs and active experts,
        #: on the device; None for a model without a held-expert layer
        self.moe_counts = None
        self._moe_totals = (jax.jit(model.held_counts)
                            if model.held_counts else None)

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
        """(b, 1) next tokens from (b, s, V) logits; traced inside the
        serving programs, with ``temperature`` static."""
        if temperature == 0.0:
            return jnp.argmax(logits[:, -1], axis=-1)[:, None]
        probs = jax.nn.softmax(logits[:, -1].astype(jnp.float32)
                               / temperature, axis=-1)
        return jax.random.categorical(
            key, jnp.log(probs + 1e-9), axis=-1)[:, None]

    def generate(self, tokens, n_new: int, temperature: float = 0.0,
                 key=None, extras: dict | None = None):
        """tokens: (b, s0) int32 prompt. Returns the token buffer, still on
        the device: (b, ``max_len``) int32 whose columns s0 .. s0+n_new-1
        hold the generated ids by sequence position (the prompt's columns
        are 0). No step waits for the host: each samples inside its program
        and writes into the buffer, so the tokens reach the host only when
        the caller reads the buffer. Spans ``serve.prefill`` (new caches,
        prefill, first sample) and one ``serve.decode_step`` per further
        token (the dispatch of ``_decode``)."""
        b, s0 = tokens.shape
        assert s0 + n_new <= self.max_len, (s0, n_new, self.max_len)
        key = jax.random.PRNGKey(0) if key is None else key
        with self.mesh_context():
            with obs.span("serve.prefill"):
                caches = self.new_caches(b)
                batch = {"tokens": tokens, **(extras or {})}
                logits, caches = self.prefill(self.params, batch, caches)
                tok, out = self.first(logits, key, np.int32(s0), temperature)
                if self._moe_totals is not None:
                    after_prefill = self._moe_totals(caches)
            for i in range(1, n_new):
                with obs.span("serve.decode_step", step=i):
                    caches, tok, key, out = self.decode(
                        self.params, caches, tok, np.int32(s0 + i - 1), key,
                        out, temperature)
            if self._moe_totals is not None:
                self.moe_counts = (after_prefill, self._moe_totals(caches))
        return out


def left_pad(prompts: list[list[int]]):
    """(b, longest) int32 batch of ``prompts``, left-padded with token 0."""
    s_max = max(len(p) for p in prompts)
    return jnp.asarray([[0] * (s_max - len(p)) + p for p in prompts],
                       jnp.int32)


@dataclasses.dataclass
class ServeCounts:
    """What a :class:`BatchedServer` has served, counted per batch."""
    batches: int = 0
    requests: int = 0
    prompt_tokens: int = 0      # the requests' own prompt tokens
    pad_tokens: int = 0         # left padding up to each batch's longest
    new_tokens: int = 0         # tokens the engine made, rows x steps
    readbacks: int = 0          # device-to-host transfers of token buffers
    #: held-expert layers only, by call kind ("prefill", "decode"): routed
    #: (token, expert) pairs the held experts computed, and held experts
    #: that got at least one pair, summed over layers and calls
    moe_pairs: dict = dataclasses.field(default_factory=dict)
    moe_active: dict = dataclasses.field(default_factory=dict)


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
        self.counts = ServeCounts()

    def submit(self, req: Request):
        self._queue.put(req)

    def drain(self) -> list[Request]:
        """Serve everything currently queued; returns completed requests.

        Each bucket is one ``serve.batch`` span (:mod:`repro.obs`) around
        the engine's ``serve.prefill`` and ``serve.decode_step`` spans and a
        ``serve.readback`` span. The bucket's tokens stay in the engine's
        device buffer until its last step is done; the read-back span then
        moves the buffer (and any held-expert counts) to the host in one
        transfer and slices each request's tokens there, so that it times
        the transfer and not the wait for queued steps; :attr:`counts` adds
        it up."""
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
            c = self.counts
            n_new = max(r.max_new for r in bucket)
            prompt = [len(r.tokens) for r in bucket]
            with obs.span("serve.batch", batch=c.batches, rows=len(bucket),
                          uids=tuple(r.uid for r in bucket),
                          prompt_len=max(prompt), n_new=n_new):
                out = self.engine.generate(
                    left_pad([r.tokens for r in bucket]), n_new)
                jax.block_until_ready((out, self.engine.moe_counts))
                with obs.span("serve.readback",
                              tokens=sum(r.max_new for r in bucket)):
                    gen, moe = jax.device_get((out, self.engine.moe_counts))
                    s0 = max(prompt)        # the padded prompt's width
                    for i, r in enumerate(bucket):
                        r.result = gen[i, s0:s0 + r.max_new].tolist()
                    if moe is not None:
                        self._count_moe(*moe)
                c.readbacks += 1
            for r in bucket:
                r.batch, r.done = c.batches, True
                done.append(r)
            c.batches += 1
            c.requests += len(bucket)
            c.prompt_tokens += sum(prompt)
            c.pad_tokens += len(bucket) * max(prompt) - sum(prompt)
            c.new_tokens += len(bucket) * n_new
        return done

    def _count_moe(self, after_prefill, after_decode) -> None:
        c = self.counts
        for kind, (pairs, active) in (
                ("prefill", after_prefill),
                ("decode", after_decode - after_prefill)):
            c.moe_pairs[kind] = c.moe_pairs.get(kind, 0) + int(pairs)
            c.moe_active[kind] = c.moe_active.get(kind, 0) + int(active)
