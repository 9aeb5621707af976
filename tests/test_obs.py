"""Program spans (``repro.obs``): nesting, the bounded ring, the serving
engine's spans per batch, and the same spans in a profiler trace."""
import pathlib
import tempfile
import threading

import jax
import pytest

from repro import configs, obs
from repro.launch import serve as serve_mod
from repro.models.common import materialize
from repro.models.lm import LM
from repro.serve import Engine


@pytest.fixture(scope="module")
def engine():
    cfg = configs.reduced(configs.get_config("granite-8b"))
    model = LM(cfg)
    params = materialize(model.param_recs(), jax.random.PRNGKey(0))
    return cfg, Engine(model, params, max_len=64)


def _since(first_id):
    return [r for r in obs.spans() if r.id >= first_id]


def _next_id():
    with obs.span("test.mark") as s:
        pass
    return s.id + 1


def test_span_nests_parents():
    with obs.span("test.outer", k=1) as outer:
        with obs.span("test.mid") as mid:
            with obs.span("test.inner") as inner:
                pass
        with obs.span("test.sibling") as sib:
            pass
    recs = {r.id: r for r in _since(outer.id)}
    assert recs[outer.id].parent is None and recs[outer.id].attrs == {"k": 1}
    assert recs[mid.id].parent == outer.id
    assert recs[inner.id].parent == mid.id
    assert recs[sib.id].parent == outer.id
    o, i = recs[outer.id], recs[inner.id]
    assert o.start_ns <= i.start_ns <= i.end_ns <= o.end_ns


def test_span_parents_are_per_thread():
    got = {}

    def other():
        with obs.span("test.thread") as s:
            got["span"] = s

    with obs.span("test.main"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert got["span"].parent is None


def test_span_is_recorded_when_the_body_raises():
    with pytest.raises(ValueError):
        with obs.span("test.raises") as s:
            raise ValueError
    assert [r.name for r in _since(s.id)] == ["test.raises"]


def test_ring_is_bounded():
    first = _next_id()
    for i in range(obs.RING_SIZE + 10):
        with obs.span("test.fill", i=i):
            pass
    recs = obs.spans()
    assert len(recs) == obs.RING_SIZE
    assert recs[-1].attrs == {"i": obs.RING_SIZE + 9}
    assert recs[0].id == first + 10


def test_serve_records_one_batch_per_bucket(engine):
    cfg, eng = engine
    first = _next_id()
    reqs = serve_mod.make_requests(cfg.vocab, 7, (4, 9), 5, seed=1)
    res = serve_mod.serve(eng, reqs, batch_size=3)
    recs = _since(first)
    batches = [r for r in recs if r.name == "serve.batch"]
    assert [b.attrs["rows"] for b in batches] == res.batches == [3, 3, 1]
    assert [b.attrs["batch"] for b in batches] == [0, 1, 2]
    for b in batches:
        kids = [r for r in recs if r.parent == b.id]
        names = [r.name for r in sorted(kids, key=lambda r: r.start_ns)]
        assert names == (["serve.prefill"] + ["serve.decode_step"] * 4
                         + ["serve.readback"])
        assert b.attrs["n_new"] == 5
        assert b.attrs["uids"] == tuple(r.uid for r in res.done
                                        if r.batch == b.attrs["batch"])
        assert all(b.start_ns <= k.start_ns <= k.end_ns <= b.end_ns
                   for k in kids)
        steps = [k.attrs["step"] for k in kids
                 if k.name == "serve.decode_step"]
        assert steps == [1, 2, 3, 4]
    c = res.counts
    assert (c.batches, c.requests, c.new_tokens) == (3, 7, 35)
    assert c.prompt_tokens == sum(len(r.tokens) for r in reqs)
    assert c.pad_tokens == sum(b.attrs["rows"] * b.attrs["prompt_len"]
                               for b in batches) - c.prompt_tokens


def test_serve_spans_reach_the_profiler_trace(engine):
    from jax.profiler import ProfileData
    cfg, eng = engine
    reqs = serve_mod.make_requests(cfg.vocab, 2, (4, 6), 3, seed=2)
    serve_mod.serve(eng, reqs, batch_size=2)      # compile outside
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            with jax.profiler.TraceAnnotation("test.around"):
                serve_mod.serve(eng, reqs, batch_size=2)
        finally:
            jax.profiler.stop_trace()
        pb = sorted(pathlib.Path(d).rglob("*.xplane.pb"))[-1]
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for p in ProfileData.from_file(str(pb)).planes
                  for line in p.lines for e in line.events
                  if e.name.startswith(("serve.", "test.around"))]

    def inside(name, outer):
        (_, s, e), = [x for x in events if x[0] == outer]
        return [x for x in events if x[0] == name and s <= x[1] <= x[2] <= e]

    assert len(inside("serve.batch", "test.around")) == 1
    assert len(inside("serve.prefill", "serve.batch")) == 1
    assert len(inside("serve.decode_step", "serve.batch")) == 2
    assert len(inside("serve.readback", "serve.batch")) == 1
