"""The harness end to end on a tiny cell on the CPU, past its look for a
chip: a clean run is correct, at the engine's default decode horizon and
at two tokens per call; a run whose timed path is broken underneath comes
out not correct, once for each fault a served cell can have (a token
altered where it is sampled, a step score altered where it is computed);
the control (the reference below the stated precision, in the engine's
place) comes out not correct on the same tokens and limits; and the
weights, the reference and the counts come from the configuration's
family, which a run refuses to go without."""
import re
import time
import types

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


class Recording:
    """One module of a family, seen through a stub that notes every name
    read from it (a call of the reference with ``low`` as ``forward(low)``)."""

    def __init__(self, part, mod, seen):
        self._part, self._mod, self._seen = part, mod, seen

    def __getattr__(self, name):
        real = getattr(self._mod, name)
        if not callable(real):
            self._seen.add(f"{self._part}.{name}")
            return real

        def call(*a, **kw):
            low = "(low)" if kw.get("low") else ""
            self._seen.add(f"{self._part}.{name}{low}")
            return real(*a, **kw)
        return call


def test_family_comes_from_files():
    """A family handed in through ``files`` (a stub over qwen3's three
    modules) makes the weights, runs the reference plain and in the
    control, gives the trace reduction its kernel names and the readers
    their counts: nothing is imported by a fixed name."""
    qwen3 = harness.load_family(tiny.files()["config"])
    seen = set()
    stub = types.SimpleNamespace(**{
        part: Recording(part, getattr(qwen3, part), seen)
        for part in harness.FAMILY_MODULES})
    out = harness.run("tiny", SEED, 2.0, True, t_process=time.perf_counter(),
                      require_chip=False, control=True,
                      files={**tiny.files(), "family": stub})
    assert out["correct"], out["check"]
    assert {"weights.make_weights", "reference.forward",
            "reference.forward(low)", "reference.BLOCK", "cost.KERNELS",
            "cost.token_flops"} <= seen, seen
    assert "step_mfu" in out["metrics"]


@pytest.mark.parametrize("family, missing", [
    (None, "bench/families/"),
    ("absent", "bench/families/absent/"),
    ("half", "bench/families/half/reference.py"),
])
def test_config_without_family_is_refused(family, missing, tmp_path,
                                          monkeypatch):
    """No ``family`` in the configuration, a family with no directory, and
    one whose directory lacks a module: each is refused, naming the path
    it looked for."""
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    half = tmp_path / "bench" / "families" / "half"
    half.mkdir(parents=True)
    (half / "weights.py").write_text("def make_weights(*a):\n    pass\n")
    files = tiny.files()
    del files["config"]["family"]
    if family is not None:
        files["config"]["family"] = family
    with pytest.raises(harness.Refused, match=re.escape(missing)):
        harness.run("tiny", SEED, 2.0, False, t_process=time.perf_counter(),
                    require_chip=False, files=files)
