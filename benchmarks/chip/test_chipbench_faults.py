"""CPU runs of the on-chip benchmark at a tiny size with the timed path broken
underneath: a step that returns its state unchanged, half the batch left out,
the replay rows left out, a record altered where the buffer writes it. Each
run must come out not correct. The control (the reference in float8 operands in the program's place)
must fail a limit of the cells."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import compare, reference  # noqa: E402
from test_chipbench_runs import SEED, _run  # noqa: E402


def _frozen(ent):
    """A step that returns the state it was given (parameters, moments)."""
    orig = ent.step_fn

    def step(carry, batch, key):
        keep = jax.tree_util.tree_map(jnp.copy, (carry.params, carry.opt))
        new, metrics = orig(carry, batch, key)
        return new._replace(params=keep[0], opt=keep[1]), metrics

    ent.step_fn = step


def test_state_left_unchanged_is_not_correct():
    res = _run("cnn", hook=_frozen)
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    from repro.models import model_zoo

    orig = model_zoo.cross_entropy

    def half(logits, labels, *a, **k):
        n = logits.shape[0] // 2
        return orig(logits[:n], labels[:n], *a, **k)

    monkeypatch.setattr(model_zoo, "cross_entropy", half)
    res = _run("cnn")
    assert not res["correct"]


def _no_replay_cnn(monkeypatch):
    from repro.buffer import state

    orig = state.augment_batch
    monkeypatch.setattr(state, "augment_batch", lambda batch, reps, valid, *a, **k: orig(
        batch, reps, jnp.zeros_like(valid), *a, **k))


def _no_replay_lm(monkeypatch):
    from repro.core import distributed

    orig = distributed.augment_global
    monkeypatch.setattr(distributed, "augment_global",
                        lambda batch, reps, valid, *a, **k: orig(
                            batch, reps, jnp.zeros_like(valid), *a, **k))


@pytest.mark.parametrize("name", ["cnn", "lm"])
def test_replay_rows_left_out_are_not_correct(name, monkeypatch):
    """The augment masks every representative: the step trains the new rows
    alone while the buffer and the sample go on as before."""
    {"cnn": _no_replay_cnn, "lm": _no_replay_lm}[name](monkeypatch)
    res = _run(name)
    assert not res["correct"]
    assert res["checks"]["buffer_mismatches"]["value"] == 0


@pytest.mark.parametrize("name", ["cnn", "lm"])
def test_record_altered_where_the_buffer_writes_it_is_not_correct(name, monkeypatch):
    from repro.buffer import api

    orig = api.local_update

    def altered(state, items, *a, **k):
        items = dict(items)
        field = "images" if "images" in items else "tokens"
        items[field] = items[field] + 1
        return orig(state, items, *a, **k)

    monkeypatch.setattr(api, "local_update", altered)
    res = _run(name)
    assert not res["correct"]
    assert res["checks"]["buffer_mismatches"]["value"] > 0


@pytest.mark.parametrize("name", ["cnn", "lm"])
def test_control_fails_a_limit(name):
    """The reference with float8 operands, in the program's place, is held to
    the cell's limits and fails one of them."""
    cap = {}
    _run(name, capture=cap)
    spec = cap["spec"]
    out = reference.replay(spec["config"], spec["traffic"], cap["family"],
                           cap["layout"], SEED, spec["traffic"]["checked_steps"],
                           qdt="float8_e4m3fn")
    numbers, _ = compare.training_numbers(out, cap["ref"])
    assert any(numbers[k] > v for k, v in spec["limits"].items() if k != "set_from"), numbers
