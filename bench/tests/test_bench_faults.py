"""A run over a broken timed path comes out as not correct.

Each test drives the harness's run of a tiny cell on the CPU (no look for a
chip) with one fault planted in the program underneath: a step that returns
its state unchanged, half of each batch left out and the rest scaled up, an
answer altered where it is produced. The cells are one chip, so there is no
exchange between chips to leave out. A sound run of the same cell comes out
correct.
"""

from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("faults")))


def _mine_unchanged(monkeypatch):
    import repro.core.streaming as streaming

    monkeypatch.setattr(streaming, "make_accum_count_step",
                        lambda mesh, cfg: (lambda t, c, ln, acc: acc))


def _mine_half_batch(monkeypatch):
    import repro.core.apriori as ap
    import repro.core.streaming as streaming

    def make(mesh, cfg):
        count = ap.make_count_step(mesh, cfg)
        return lambda t, c, ln, acc: acc + 2 * count(t[: t.shape[0] // 2], c, ln)

    monkeypatch.setattr(streaming, "make_accum_count_step", make)


def _mine_altered(monkeypatch):
    import repro.serving.rulebook as rulebook

    orig = rulebook.compile_rulebook

    def altered(*a, **k):
        rb = orig(*a, **k)
        rb.scores[0] *= 1.001
        return rb

    monkeypatch.setattr(rulebook, "compile_rulebook", altered)


def _serve_unchanged(monkeypatch):
    import repro.serving.gateway as gateway

    monkeypatch.setattr(gateway, "_cached_match_step",
                        lambda *a: (lambda b, *r: jnp.zeros((b.shape[0], 32 * b.shape[1]))))


def _serve_half_batch(monkeypatch):
    import repro.serving.gateway as gateway

    orig = gateway._cached_match_step

    def make(*a):
        step = orig(*a)

        def half(b, *r):
            out = step(b, *r)
            keep = jnp.arange(b.shape[0])[:, None] < max(1, b.shape[0] // 2)
            return jnp.where(keep, out, 0.0)
        return half

    monkeypatch.setattr(gateway, "_cached_match_step", make)


def _serve_altered(monkeypatch):
    import repro.serving.gateway as gateway

    orig = gateway._topk_items

    def altered(*a, **k):
        idx, vals = orig(*a, **k)
        return (idx + 1) % k["num_items"], vals

    monkeypatch.setattr(gateway, "_topk_items", altered)


@pytest.mark.parametrize("name,fault", [
    ("mine.tiny", None), ("mine.tiny", _mine_unchanged), ("mine.tiny", _mine_half_batch),
    ("mine.tiny", _mine_altered),
    ("serve.tiny.steady", None), ("serve.tiny.steady", _serve_unchanged),
    ("serve.tiny.steady", _serve_half_batch), ("serve.tiny.steady", _serve_altered),
], ids=lambda x: getattr(x, "__name__", x))
def test_fault_makes_the_run_incorrect(root, monkeypatch, name, fault):
    if fault is not None:
        fault(monkeypatch)
    out = tiny.run(root, name)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0
