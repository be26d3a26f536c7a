"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a cell can have, and when the control (the reference
computed in float8) takes the program's place.

The faults are planted in the program (by monkeypatching what the jobs
call), and each run goes through the harness exactly as a chip run does,
at smoke size, under the smoke limits that ``test_bench_tpu_jobs`` shows a
sound run to pass.  A one-chip cell has no exchange between chips to leave
out.
"""
from __future__ import annotations

import pytest

from bench_tpu_smoke import run, smoke_cell

TRAIN = "train.smollm-135m.16x2048"
EAGER = "eager.smollm-135m.ffn30.16x2048.b050"


def _broken_step(monkeypatch, wrap):
    from repro.launch import steps
    real = steps.make_train_step
    monkeypatch.setattr(steps, "make_train_step",
                        lambda *a, **k: wrap(real(*a, **k)))


def _state_unchanged(monkeypatch):
    def wrap(step):
        def broken(params, opt_state, batch, max_loss):
            _, _, metrics = step(params, opt_state, batch, max_loss)
            return params, opt_state, metrics
        return broken
    _broken_step(monkeypatch, wrap)


def _half_batch(monkeypatch):
    def wrap(step):
        def broken(params, opt_state, batch, max_loss):
            t = batch["tokens"]
            half = ({"tokens": t[: t.shape[0] // 2]} if t.shape[0] > 1
                    else {"tokens": t[:, : t.shape[1] // 2]})
            return step(params, opt_state, half, max_loss)
        return broken
    _broken_step(monkeypatch, wrap)


def _grad_doubled(monkeypatch):
    """One leaf's gradient doubled inside the compiled step, as the chip
    calibration plants it."""
    import repro.optim
    from benchmarks.tpu import calibrate
    monkeypatch.setattr(repro.optim, "adamw",
                        calibrate.doubling_adamw(repro.optim.adamw))


def _eager_half_batch(monkeypatch):
    from repro.eager import DTRContext
    real = DTRContext.wrap

    def wrap(self, x, constant=True, name="const"):
        if name == "x0":
            x = x[: x.shape[0] // 2]
        return real(self, x, constant, name)
    monkeypatch.setattr(DTRContext, "wrap", wrap)


def _eager_answer_altered(monkeypatch):
    from repro.eager import DTRContext
    real = DTRContext.call

    def call(self, name, fn, args, n_outputs=None):
        if name == "d_wo":
            f = fn
            fn = lambda *a: 2 * f(*a)   # noqa: E731
        return real(self, name, fn, args, n_outputs)
    monkeypatch.setattr(DTRContext, "call", call)


@pytest.mark.parametrize("workload,plant", [
    (TRAIN, _state_unchanged),
    (TRAIN, _half_batch),
    (TRAIN, _grad_doubled),
    (EAGER, _eager_half_batch),
    (EAGER, _eager_answer_altered),
], ids=["train-state-unchanged", "train-half-batch", "train-grad-doubled",
        "eager-half-batch", "eager-answer-altered"])
def test_fault_makes_the_run_incorrect(monkeypatch, workload, plant):
    plant(monkeypatch)
    res = run(smoke_cell(workload))
    assert res["correct"] is False, res["checks"]


def _train_control(monkeypatch):
    """The float8 reference's readings in the program's place."""
    from benchmarks.tpu.kinds import train
    real = train.Job.setup

    def setup(self):
        real(self)
        self.prog = self.reference_readings("fp8")
    monkeypatch.setattr(train.Job, "setup", setup)


def _eager_control(monkeypatch):
    """The float8 reference's gradients in place of each step's."""
    from benchmarks.tpu.kinds import eager
    real = eager.Job.step

    def step(self, i):
        n = real(self, i)
        self.norms[-1] = self.reference_norms(i, "fp8")
        return n
    monkeypatch.setattr(eager.Job, "step", step)


@pytest.mark.parametrize("workload", [TRAIN])
def test_train_control_fails_the_limits(monkeypatch, workload):
    _train_control(monkeypatch)
    res = run(smoke_cell(workload))
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", [EAGER])
def test_eager_control_fails_the_limits(monkeypatch, workload):
    _eager_control(monkeypatch)
    res = run(smoke_cell(workload))
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["grad_gap"]["value"] > res["checks"]["grad_gap"][
        "limit"]
