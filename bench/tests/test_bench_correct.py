"""The comparison that decides ``correct``, on the CPU at a tiny size.

Each test drives a whole run (set-up, the timed window through the
frontend, the reference check) without the harness's look for a chip,
with the timed path sound, switched to the lower-precision control, or
broken underneath, and reads ``correct``.
"""
import copy
import time

import numpy as np
import pytest

from bench import run as bench_run

TINY_LAYERS = [
    dict(c_in=16, c_out=64, kernel=4, stride=1, padding=0, activation="relu"),
    dict(c_in=64, c_out=32, kernel=4, stride=2, padding=1, activation="relu"),
    dict(c_in=32, c_out=16, kernel=4, stride=2, padding=1, activation="relu"),
    dict(c_in=16, c_out=3, kernel=4, stride=2, padding=1, activation="tanh"),
]


def tiny_spec(workload="celeba-bulk"):
    """The cell with its configuration's limits, at a size the CPU runs
    in interpret mode: narrower layers, an 8-row bucket.  The CPU takes
    the kernels' float32 dot operands at full precision, where the chip
    rounds them to bfloat16, so the tiny configuration states that."""
    spec = bench_run.Spec(workload)
    spec.cfg = copy.deepcopy(spec.cfg)
    spec.cfg.update(z_dim=16, img_hw=32, img_c=3, layers=TINY_LAYERS,
                    dot_operands="float32")
    spec.cfg["engine"]["max_batch"] = 8
    spec.mix = dict(spec.mix, rows={"dist": "const", "value": 8},
                    check_requests=4)
    return spec


def run(spec, **kw):
    return bench_run.run_cell(spec, 2**35 + 9, 0.5, False,
                              time.perf_counter(), log=lambda m: None, **kw)


@pytest.fixture
def engine_cls():
    import repro.serve.engine as engine
    return engine.DcnnServeEngine


def test_sound_run_is_correct():
    line = run(tiny_spec())
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    checks = line["checks"]
    assert line["checked_rows"] == 5 * 8
    assert list(line)[-1] == "checks"
    assert {"max_gap", "rms_gap", "lost_requests"} <= set(checks)


def test_bf16_control_is_not_correct():
    """The plain reference in bfloat16 throughout, in the program's place
    on the rows the run served."""
    line = run(tiny_spec(), controls=("bf16",))
    assert line["correct"] is True, line["checks"]
    control = line["control_checks"]["bf16"]
    assert bench_run.is_correct(control) is False, control


def test_int8_control_is_not_correct():
    """The program's int8 path in the float32 path's place."""
    line = run(tiny_spec(), precision="int8")
    assert line["correct"] is False, line["checks"]


def test_answer_altered_where_produced(monkeypatch, engine_cls):
    real = engine_cls.generate

    def altered(self, z):
        y = np.array(real(self, z))
        y[-1, 3, 5, 1] += 0.25
        return y

    monkeypatch.setattr(engine_cls, "generate", altered)
    line = run(tiny_spec())
    assert line["correct"] is False
    assert line["checks"]["max_gap"]["value"] > line["checks"]["max_gap"][
        "limit"]


def test_answers_given_to_the_wrong_rows(monkeypatch, engine_cls):
    real = engine_cls.generate
    monkeypatch.setattr(engine_cls, "generate",
                        lambda self, z: np.roll(real(self, z), 1, axis=0))
    assert run(tiny_spec())["correct"] is False


def test_half_the_rows_left_unserved(monkeypatch, engine_cls):
    """Only the first half of each wave is computed; the rest is zeros."""
    real = engine_cls.generate

    def half(self, z):
        y = np.zeros((len(z),) + real(self, z[:1]).shape[1:], np.float32)
        h = max(1, len(z) // 2)
        y[:h] = real(self, z[:h])
        return y

    monkeypatch.setattr(engine_cls, "generate", half)
    assert run(tiny_spec())["correct"] is False


def test_answer_that_never_comes(monkeypatch):
    from repro.serve import frontend

    real = frontend.AsyncServeFrontend.result
    calls = []

    def lose_one(self, rid, timeout_s=None):
        calls.append(rid)
        if len(calls) == 3:
            raise frontend.DeadlineExceeded("lost")
        return real(self, rid, timeout_s)

    monkeypatch.setattr(frontend.AsyncServeFrontend, "result", lose_one)
    line = run(tiny_spec())
    assert line["checks"]["lost_requests"]["value"] == 1
    assert line["failed"] == 1 and line["correct"] is False
