"""Serving launcher: one architecture behind the batched request driver.

    PYTHONPATH=src python -m repro.launch.serve --reduced --requests 8 \\
        --prompt-len 8 32 --max-new 8 --max-len 64

Without ``--reduced`` the architecture runs at its published widths and
depth (phi3-mini-3.8b by default: 7.6 GB of bf16 weights), which needs an
accelerator; ``--reduced`` swaps in the family-preserving smoke config
that the CPU tests and examples use. Weights are random, from a fixed seed.
The defaults are the one-chip serving run of ``chip_smoke.py``: 8 requests
of 256-1024 prompt tokens, 32 new tokens each, batch 4, ``max_len`` 2048.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models.common import materialize
from repro.models.lm import LM
from repro.serve import Engine
from repro.serve.engine import BatchedServer, Request, left_pad


@dataclasses.dataclass
class ServeResult:
    done: list[Request]
    seconds: float              # host clock, submit to last token ready
    tokens: int                 # generated tokens
    batches: list[int]          # requests per engine batch


def build(arch: str, *, reduced: bool = False):
    """(cfg, model, params): the published config unless ``reduced``,
    random weights from seed 0."""
    cfg = configs.get_config(arch)
    if reduced:
        cfg = configs.reduced(cfg)
    model = LM(cfg)
    params = materialize(model.param_recs(), jax.random.PRNGKey(0))
    return cfg, model, params


def make_requests(vocab: int, n: int, prompt_len: tuple[int, int],
                  max_new: int, seed: int = 0) -> list[Request]:
    """``n`` requests with prompt lengths drawn uniformly from
    ``prompt_len`` (inclusive) and random token ids, from ``seed``."""
    rng = np.random.default_rng(seed)
    lo, hi = prompt_len
    return [Request(uid=i,
                    tokens=rng.integers(0, vocab, int(rng.integers(
                        lo, hi + 1))).tolist(),
                    max_new=max_new)
            for i in range(n)]


def serve(engine: Engine, requests: list[Request], *,
          batch_size: int) -> ServeResult:
    """Serve ``requests`` through :class:`BatchedServer` on ``engine``."""
    server = BatchedServer(engine, batch_size=batch_size)
    t0 = time.perf_counter()
    for r in requests:
        server.submit(r)
    done = server.drain()           # token ids reach the host: all ready
    dt = time.perf_counter() - t0
    return ServeResult(done=done, seconds=dt,
                       tokens=sum(len(r.result) for r in done),
                       batches=list(server._served))


def replay_logits(engine: Engine, requests: list[Request],
                  batch_size: int) -> dict[int, np.ndarray]:
    """The logits greedy serving picks each generated token from.

    ``requests`` go through ``engine`` again in the batches
    :class:`BatchedServer` forms (submission order, left-padded), by
    prefill and cached decode, with each request's ``result`` fed back one
    token at a time. On the engine that served them, the argmax of each
    row is the served token; the tokens may also come from another
    engine's run, so one run's tokens test another placement's decode at
    every position. Returns ``{uid: (len(result), vocab) float32}`` over
    the real (unpadded) vocabulary."""
    vocab = engine.model.cfg.vocab
    out = {}
    for i in range(0, len(requests), batch_size):
        bucket = requests[i:i + batch_size]
        toks = left_pad([r.tokens for r in bucket])
        s0 = toks.shape[1]
        n = max(len(r.result) for r in bucket)
        fed = jnp.asarray([r.result + [0] * (n - len(r.result))
                           for r in bucket], jnp.int32)
        with engine.mesh_context():
            caches = engine.new_caches(len(bucket))
            logits, caches = engine.prefill(engine.params, {"tokens": toks},
                                            caches)
            steps = [logits[:, -1, :vocab]]
            for j in range(1, n):
                logits, caches = engine.decode(engine.params, caches,
                                               fed[:, j - 1:j],
                                               jnp.int32(s0 + j - 1))
                steps.append(logits[:, -1, :vocab])
        got = np.asarray(jnp.stack(steps, axis=1), np.float32)
        for r, row in zip(bucket, got):
            out[r.uid] = row[:len(r.result)]
    return out


def unpadded(result: ServeResult, batch_size: int) -> Request:
    """A request that was served without padding: the longest prompt of
    the first batch (``BatchedServer`` left-pads to the batch's longest)."""
    return max(result.done[:batch_size], key=lambda r: len(r.tokens))


def forward_logit_error(model, params, req: Request,
                        logits: np.ndarray) -> dict:
    """Compare ``logits``, the ones ``req``'s tokens were picked from by
    prefill plus cached decode (:func:`replay_logits`), with one full
    ``model.forward`` over the same tokens, in float32 over the real
    vocabulary. ``req`` must have been served without padding."""
    vocab = model.cfg.vocab
    seq = jnp.asarray([req.tokens + req.result[:-1]], jnp.int32)
    full = jax.jit(model.forward)(params, {"tokens": seq})
    s0 = len(req.tokens)
    want = np.asarray(full[0, s0 - 1:s0 - 1 + len(req.result), :vocab],
                      np.float32)
    return {"max_abs_err": float(np.max(np.abs(logits - want))),
            "max_abs_logit": float(np.max(np.abs(want))),
            "argmax_agree": float(np.mean(
                np.argmax(logits, -1) == np.argmax(want, -1))),
            "positions": int(logits.shape[0])}


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b",
                    choices=configs.ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="the family-preserving smoke config (CPU)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(256, 1024),
                    metavar=("MIN", "MAX"))
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--batch-size", type=int, default=4)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg, model, params = build(args.arch, reduced=args.reduced)
    reqs = make_requests(cfg.vocab, args.requests, tuple(args.prompt_len),
                         args.max_new)
    engine = Engine(model, params, max_len=args.max_len)
    res = serve(engine, reqs, batch_size=args.batch_size)
    print(f"[serve] {args.arch}{' reduced' if args.reduced else ''}: "
          f"{len(res.done)} requests, {res.tokens} tokens in "
          f"{res.seconds:.2f}s ({res.tokens / res.seconds:.1f} tok/s, "
          f"compiles included), batches={res.batches}")
    for r in res.done[:3]:
        print(f"  req {r.uid}: {len(r.tokens)} prompt tokens -> "
              f"{r.result[:8]}...")


if __name__ == "__main__":
    main()
