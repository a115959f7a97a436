"""``correct`` has to come out false when it should: for the
lower-precision controls (at the tiny preset, on the CPU; the readings
on the chip at the cells' own size are in PERF.md), and for a timed
path broken underneath the harness. These skip only the harness's look
for a chip (``--rehearse``) and drive the rest of a run."""
import json

import numpy as np
import pytest

from benchmarks import run as bench_run

SERVE, TRAIN = "mistral7b-chat-steady", "mistral7b-train-2k"


def last_line(capsys, *argv):
    bench_run.main(["--rehearse", "--trace", "0", *argv])
    out = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    checks = [json.loads(l)["check"] for l in out if l.startswith('{"check"')]
    return json.loads(out[-1]), checks


def failed(checks):
    return [c["compared"] for c in checks if not c["ok"]]


def test_sound_serving_run_is_correct(capsys):
    line, checks = last_line(capsys, "--workload", SERVE, "--seed", "3",
                             "--seconds", "4")
    assert line["correct"] is True and failed(checks) == []


@pytest.mark.parametrize("control", ["weight_int8", "cache_int8"])
def test_serving_control_is_not_correct(capsys, control):
    line, checks = last_line(capsys, "--workload", SERVE, "--seed", "1",
                             "--seconds", "4", "--control", control)
    assert line["correct"] is False
    assert any("gap" in name for name in failed(checks))


def test_altered_token_is_not_correct(capsys, monkeypatch):
    """A token altered where it is produced: every fifth decode step
    hands the requests a token one higher than the one it sampled."""
    from paddle_tpu.inference.serving import ServingEngine
    real = ServingEngine._run_decode
    calls = {"n": 0}

    def broken(self):
        before = {id(s.req): len(s.req.tokens) for s in self._slots
                  if s.req is not None}
        did = real(self)
        calls["n"] += 1
        if calls["n"] % 5 == 0:
            for s in list(self._slots) + [None]:
                req = getattr(s, "req", None)
                if req is not None and len(req.tokens) > before.get(
                        id(req), 1 << 30):
                    req.tokens[-1] = (req.tokens[-1] + 1) % \
                        self.cfg.vocab_size
        return did

    monkeypatch.setattr(ServingEngine, "_run_decode", broken)
    line, checks = last_line(capsys, "--workload", SERVE, "--seed", "3",
                             "--seconds", "4")
    assert line["correct"] is False
    assert any("gap" in name for name in failed(checks))


def test_sound_training_run_is_correct(capsys):
    line, checks = last_line(capsys, "--workload", TRAIN, "--seed", "3",
                             "--seconds", "2")
    assert line["correct"] is True and failed(checks) == []


def test_training_control_is_not_correct(capsys):
    line, checks = last_line(capsys, "--workload", TRAIN, "--seed", "3",
                             "--seconds", "2", "--control", "ref_fp8")
    assert line["correct"] is False and failed(checks)


def test_step_that_leaves_out_a_row_is_not_correct(capsys, monkeypatch):
    """Part of the batch left out: the second row is a copy of the
    first. Only the loss can see it."""
    from paddle_tpu.distributed.trainer import Trainer
    real = Trainer.step

    def broken(self, state, tokens, labels):
        tokens = tokens.at[1].set(tokens[0])
        labels = labels.at[1].set(labels[0])
        return real(self, state, tokens, labels)

    monkeypatch.setattr(Trainer, "step", broken)
    line, checks = last_line(capsys, "--workload", TRAIN, "--seed", "3",
                             "--seconds", "2")
    assert line["correct"] is False
    assert any("loss" in name for name in failed(checks))


def test_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    from paddle_tpu.distributed.trainer import Trainer
    real_init, real_step = Trainer.__init__, Trainer.step

    def init(self, *a, **kw):
        real_init(self, *a, **{**kw, "donate": False})

    def frozen(self, state, *batch):
        _, metrics = real_step(self, state, *batch)
        return state, metrics

    monkeypatch.setattr(Trainer, "__init__", init)
    monkeypatch.setattr(Trainer, "step", frozen)
    line, checks = last_line(capsys, "--workload", TRAIN, "--seed", "3",
                             "--seconds", "2")
    assert line["correct"] is False
    assert any("change" in name or "gradient" in name
               for name in failed(checks))
    assert np.isfinite(line["metrics"]["train_tok_s"]["value"])
