"""A whole run of each cell kind on the CPU, with the timed path broken
underneath: ``correct`` must come out false for every fault the cell can
have, and true for the unbroken path. The look for a chip is skipped; the
rest of the run (set-up, window, check against the reference, the result
line) is the benchmark's own."""
import jax
import jax.numpy as jnp
import pytest

import repro.core
import repro.serve.bank_server as bank_server
from repro.core.meb import Ball

from conftest import run_tiny

TRAIN = "covtype-ovr.train"
SERVE = "imagenet-fc7-ovr.serve"
real_fit = repro.core.fit_bank
real_predict = bank_server.predict_bank


def state_unchanged(X, Y, cs, **kw):
    """A fit that returns its starting state: every model seeded from row 0
    and never updated."""
    b = Y.shape[0]
    cs = jnp.broadcast_to(jnp.asarray(cs, jnp.float32), (b,))
    return Ball(w=Y[:, :1] * X[0][None, :], r=jnp.zeros((b,)), xi2=1.0 / cs,
                m=jnp.ones((b,), jnp.int32))


def half_the_stream(X, Y, cs, **kw):
    n = X.shape[0] // 2
    return real_fit(X[:n], Y[:, :n], cs, **kw)


def center_altered(X, Y, cs, **kw):
    bank = real_fit(X, Y, cs, **kw)
    return bank._replace(w=bank.w.at[0, 0].add(1e-3))


def answer_altered(X, W, **kw):
    """The first row of every step answered with its best model swapped."""
    vals, ids = real_predict(X, W, **kw)
    return vals.at[0, 0].add(0.05), ids.at[0, 0].set((ids[0, 0] + 1) % W.shape[0])


def half_the_rows(*a, **kw):
    """Every other row of each step left unscored."""
    vals, ids = real_predict(*a, **kw)
    return vals.at[1::2].set(0.0), ids.at[1::2].set(0)


def test_sound_runs_are_correct(tiny_catalog):
    for cell in (TRAIN, SERVE):
        result, _, lines = run_tiny(tiny_catalog, cell)
        assert result["correct"] is True, lines
        assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("fault", [state_unchanged, half_the_stream,
                                   center_altered])
def test_training_faults_fail_the_check(tiny_catalog, monkeypatch, fault):
    monkeypatch.setattr(repro.core, "fit_bank", fault)
    result, _, lines = run_tiny(tiny_catalog, TRAIN)
    assert result["correct"] is False, lines


@pytest.mark.parametrize("fault", [answer_altered, half_the_rows])
def test_serving_faults_fail_the_check(tiny_catalog, monkeypatch, fault):
    monkeypatch.setattr(bank_server, "predict_bank", fault)
    result, _, lines = run_tiny(tiny_catalog, SERVE)
    assert result["correct"] is False, lines
