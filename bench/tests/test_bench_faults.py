"""The correctness check against faults planted under a whole harness run
(the look for a chip skipped, smoke shapes on the CPU): each must make
``correct`` false.  A serving cell can have two of the faults: a step that
returns its state unchanged, and a token altered where it is produced."""

import jax
import jax.numpy as jnp
import pytest

from bench import run as R
from bench.tests.smoke import jax_config_kept, make_root
from repro.core import engine as E


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench-faults"))


def _run(root, cell="smoke.chat", seed=4321):
    with jax_config_kept():
        return R.run(root, cell, seed, 1.5, False, require_tpu=False)


def _patch_engine(monkeypatch, fault):
    init = E.SpecEngine.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        fault(self)

    monkeypatch.setattr(E.SpecEngine, "__init__", patched)


def test_sound_run_is_correct(root):
    out = _run(root)
    assert out["correct"] and out["check"]["max_gap"] <= out["check"]["max_gap_limit"]


def test_state_left_unchanged_fails(root, monkeypatch):
    """Verification hands back the target cache it was given: the tree's K/V
    are never written, so later positions attend stale rows."""

    def fault(eng):
        verify = eng._verify

        def stale(tparams, tcache, *args):
            before = jax.tree.map(jnp.copy, tcache)  # the call donates tcache
            out = verify(tparams, tcache, *args)
            return (*out[:5], before, out[6])

        eng._verify = stale

    _patch_engine(monkeypatch, fault)
    out = _run(root)
    assert not out["correct"]
    assert out["check"]["max_gap"] > out["check"]["max_gap_limit"]


def test_token_altered_where_produced_fails(root, monkeypatch):
    """Verification's emitted tokens come out with one token changed."""

    def fault(eng):
        verify = eng._verify
        V = eng.target.cfg.vocab_size

        def altered(*args):
            out = verify(*args)
            emitted = out[3].at[:, 0].set((out[3][:, 0] + 1) % V)
            return (*out[:3], emitted, *out[4:])

        eng._verify = altered

    _patch_engine(monkeypatch, fault)
    out = _run(root, cell="smoke.single")
    assert not out["correct"]
    assert out["check"]["max_gap"] > out["check"]["max_gap_limit"]


def test_half_the_batch_left_out_fails(root, monkeypatch):
    """Verification computes the first half of the batch's rows and hands
    the second half the first half's tokens."""

    def fault(eng):
        verify = eng._verify

        def halved(*args):
            out = verify(*args)
            emitted = out[3]
            half = emitted.shape[0] // 2
            emitted = emitted.at[half:].set(emitted[: emitted.shape[0] - half])
            return (*out[:3], emitted, *out[4:])

        eng._verify = halved

    _patch_engine(monkeypatch, fault)
    out = _run(root)
    assert not out["correct"]
    assert out["check"]["max_gap"] > out["check"]["max_gap_limit"]
