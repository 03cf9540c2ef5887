"""Each cell, on the CPU at a small size, with the timed path broken
underneath: ``correct`` has to come out false for every fault the cell
can have (one chip, so no exchange between chips to leave out):

  * a step that returns its state unchanged;
  * half of the batch left out (the micro-batch's rows, the trace's
    rows, the sweep's lanes);
  * an answer altered where it is produced.
"""
import jax.numpy as jnp
import pytest

import benchtest
from repro.core import batched as B
from repro.core import compile_cache


@pytest.fixture(autouse=True)
def fresh():
    benchtest.fresh_programs()
    yield
    benchtest.fresh_programs()


def state_unchanged(monkeypatch):
    def body(st, state0, tr, cap):
        return dict(state0)
    monkeypatch.setattr(B, "_scan_body", body)


def altered_answer(monkeypatch):
    finalize = B._finalize

    def bad(st, final):
        out = finalize(st, final)
        return dict(out, accepted=out["accepted"].at[0].add(1),
                    vm_accepted=out["vm_accepted"].at[0].set(
                        ~out["vm_accepted"][0]))
    monkeypatch.setattr(B, "_finalize", bad)


def serve_half_batch(monkeypatch):
    step = B.make_decision_step

    def half(st):
        fn = step(st)

        def run(state, ev, rest, cap, batch_vi):
            E = len(ev["kind"])
            kind = ev["kind"].copy()
            kind[E // 2:] = B.PAD
            return fn(state, dict(ev, kind=kind), rest, cap, batch_vi)
        return run
    monkeypatch.setattr(B, "make_decision_step", half)


def serve_altered(monkeypatch):
    step = B.make_decision_step

    def alter(st):
        fn = step(st)

        def run(*a):
            state, rows = fn(*a)
            return state, rows.at[:, 0].add(jnp.where(rows[:, 2] > 0, 1, 0))
        return run
    monkeypatch.setattr(B, "make_decision_step", alter)


def replay_half_trace(monkeypatch):
    arrays = B.trace_arrays

    def half(events):
        tr = arrays(events)
        kind = tr["kind"].copy()
        kind[len(kind) // 2:] = B.PAD
        return dict(tr, kind=kind)
    monkeypatch.setattr(B, "trace_arrays", half)


def sweep_half_lanes(monkeypatch):
    cached = compile_cache.cached_replay_fn

    def wrap(key, build):
        fn = cached(key, build)
        if not (isinstance(key, tuple) and "sweep" in key):
            return fn

        def half(s0, tr, caps):
            out = fn(s0, tr, caps[:len(caps) // 2])
            return jnp.concatenate([out, out])[:len(caps)]
        return half
    monkeypatch.setattr(compile_cache, "cached_replay_fn", wrap)


FAULTS = [
    ("serve-openb-grmu", "state_unchanged", state_unchanged),
    ("serve-openb-grmu", "half_batch", serve_half_batch),
    ("serve-openb-grmu", "answer_altered", serve_altered),
    ("replay-openb-grmu", "state_unchanged", state_unchanged),
    ("replay-openb-grmu", "half_batch", replay_half_trace),
    ("replay-openb-grmu", "answer_altered", altered_answer),
    ("sweep-openb-baskets", "state_unchanged", state_unchanged),
    ("sweep-openb-baskets", "half_batch", sweep_half_lanes),
    ("sweep-openb-baskets", "answer_altered", altered_answer),
]


@pytest.mark.parametrize("cell,fault,plant", FAULTS,
                         ids=[f"{c}-{f}" for c, f, _ in FAULTS])
def test_fault_is_not_correct(cell, fault, plant, monkeypatch):
    plant(monkeypatch)
    res = benchtest.run(cell, seconds=0.3)
    assert not res["correct"], (fault, res["checks"])
