"""The harness end to end on a tiny cell on the CPU, past its look for a
chip: a clean run is correct, at the engine's default decode horizon and
at two tokens per call; a run whose timed path is broken underneath comes
out not correct, once for each fault a served cell can have (a token
altered where it is sampled, a step score altered where it is computed);
and the control (the reference below the stated precision, in the
engine's place) comes out not correct on the same tokens and limits."""
import time

import pytest

from bench import harness
from bench.tests import tiny

SEED = 3_000_000_019          # above 2**31, as the driver's seeds are


def run(control=False, **engine):
    return harness.run("tiny", SEED, 2.0, False, t_process=time.perf_counter(),
                       require_chip=False, files=tiny.files(**engine),
                       control=control)


@pytest.mark.parametrize("horizon", [1, 2])
def test_clean_run_is_correct(horizon):
    out = run(decode_horizon=horizon)
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"setup_s", "tokens_per_s"} <= set(out["metrics"])


def test_altered_token_is_caught(monkeypatch):
    import repro.serving.engine as engine
    real = engine.sample_logits

    def off_by_one(*a, **kw):
        tokens, conf = real(*a, **kw)
        return (tokens + 1) % 512, conf

    monkeypatch.setattr(engine, "sample_logits", off_by_one)
    out = run()
    assert not out["correct"]
    assert out["check"]["logprob_err"]["value"] > \
        out["check"]["logprob_err"]["limit"]


def test_altered_step_score_is_caught(monkeypatch):
    import repro.serving.engine as engine
    real = engine.scorer_score
    monkeypatch.setattr(engine, "scorer_score",
                        lambda p, h: 1.0 - real(p, h))
    out = run()
    assert not out["correct"]
    assert out["check"]["score_err"]["value"] > \
        out["check"]["score_err"]["limit"]


def test_control_is_not_correct(capsys):
    out = run(control=True)
    assert out["correct"], out["check"]
    err = capsys.readouterr().err.splitlines()
    assert "control correct=False" in " ".join(err)
    ctrl = {ln.split("=")[0][len("control "):]: float(ln.split("=")[1]
                                                      .split()[0])
            for ln in err if ln.startswith("control ")
            and not ln.startswith("control correct=")}
    assert any(ctrl[k] > out["check"][k]["limit"] for k in ctrl), ctrl
