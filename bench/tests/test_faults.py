"""A whole run at a small size on the CPU, sound and with the timed path
broken underneath: `correct` holds for the sound run and fails for each
fault the cell can have.  Two cells: the paper's MLP cell shrunk, and the
toy token cell under bench/tests, whose world, loss and scoring come from
its configuration's hooks."""
import os

import pytest

from bench import run

TESTS = os.path.dirname(os.path.abspath(__file__))
SMALL = {"world": {"seed": 3, "nodes": 8, "train_size": 2000,
                   "test_size": 512, "er_p": 0.5},
         "traffic": {"rounds_per_call": 4}}
CELLS = {"mlp.gossip-fp32": {"overrides": SMALL},
         "toy.gossip": {"where": TESTS}}


def _run(cell):
    return run.run(cell, 5, 0.5, False, require_chip=False, **CELLS[cell])


def _frozen_state(monkeypatch):
    from repro.engine import backends

    build = backends.build_round

    def frozen(exp):
        round_fn = build(exp)

        def same_state(params, opt, *rest):
            out = round_fn(params, opt, *rest)
            return (params, opt) + tuple(out[2:])
        return same_state

    monkeypatch.setattr(backends, "build_round", frozen)


def _half_batch(monkeypatch):
    from repro.data.pipeline import Batcher

    take = Batcher.take

    def half(self, x, y, count, step):
        xb, yb = take(self, x, y, count, step)
        return xb[:self.batch_size // 2], yb[:self.batch_size // 2]

    monkeypatch.setattr(Batcher, "take", half)


def _no_exchange(monkeypatch):
    from repro.engine.strategies import DecDiffStrategy

    monkeypatch.setattr(DecDiffStrategy, "flat_aggregate",
                        lambda self, exp, state, nb: nb.unflatten(nb.local()))


def _altered_answer(monkeypatch):
    from repro.engine import experiment

    make = experiment.make_eval_fn

    def altered(model, batch_size=512):
        score = make(model, batch_size=batch_size)

        def eval_fn(params, x, y):
            acc, loss = score(params, x, y)
            return acc, loss * 1.02
        return eval_fn

    monkeypatch.setattr(experiment, "make_eval_fn", altered)


FAULTS = {"frozen_state": _frozen_state, "half_batch": _half_batch,
          "no_exchange": _no_exchange, "altered_answer": _altered_answer}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, cell, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = _run(cell)
    assert res["correct"] is False, res["checks"]
