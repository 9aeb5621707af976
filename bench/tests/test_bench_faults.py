"""A run with the timed path broken underneath must come out not
correct: a step that leaves its state unchanged, an answer or a token
altered where it is produced, half of a batch left out."""
import pytest

from bench_tiny_root import add_tiny_cells, copy_checkout, run_cell
from bench import harness


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = copy_checkout(tmp_path_factory.mktemp("co"))
    add_tiny_cells(r)
    return r


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)


def _unchanged(ops, monkeypatch):
    monkeypatch.setattr(ops, "stencil3d7pt", lambda a, c: a + 0)
    monkeypatch.setattr(ops, "longrange3d", lambda u, v, roc, c: v + 0)


def _altered(ops, monkeypatch):
    s7, lr = ops.stencil3d7pt, ops.longrange3d
    monkeypatch.setattr(ops, "stencil3d7pt",
                        lambda a, c: s7(a, c).at[5, 8, 8].add(1.0))
    monkeypatch.setattr(ops, "longrange3d",
                        lambda u, v, roc, c: lr(u, v, roc, c)
                        .at[5, 8, 8].add(1.0))


@pytest.mark.parametrize("fault", [_unchanged, _altered])
@pytest.mark.parametrize("cell", ["stencil-tiny.longrange25pt",
                                  "stencil-tiny.jacobi7pt"])
def test_stencil_fault_is_not_correct(root, cell, fault, monkeypatch):
    from repro.kernels import ops
    fault(ops, monkeypatch)
    out = run_cell(root, cell, seconds=0.2)
    assert out["correct"] is False


def test_altered_token_is_not_correct(root, monkeypatch):
    import jax.numpy as jnp
    from repro.serve.engine import Engine

    def least_likely(self, logits, temperature, key):
        vocab = self.model.cfg.vocab
        return jnp.argmin(logits[:, -1, :vocab], axis=-1)[:, None]

    monkeypatch.setattr(Engine, "_sample", least_likely)
    assert run_cell(root, "lm-tiny.tiny-chat", seconds=0.2)["correct"] is False


def test_half_the_batch_left_out_is_not_correct(root, monkeypatch):
    from repro.serve.engine import BatchedServer
    drain = BatchedServer.drain

    def half(self):
        done = drain(self)
        return done[:len(done) // 2]

    monkeypatch.setattr(BatchedServer, "drain", half)
    out = run_cell(root, "lm-tiny.tiny-chat", seconds=0.2)
    assert out["failed"] > 0 and out["correct"] is False
