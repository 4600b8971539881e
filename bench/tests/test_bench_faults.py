"""A whole run of each cell, at a size the CPU holds and with the chip
check skipped, comes out ``correct`` when the timed path is sound and
not correct when it is broken underneath (``bench/tests/faults.py``) or
when the fp8 control is judged in the program's place."""
from __future__ import annotations

import time

import jax
import pytest

from bench import run
from bench.tests.faults import FAULTS, planted
from bench.tests.tiny import CHAT, REASONING, tiny_cell

# float32 at this size: a sound run's gaps are 0, any fault's mean is far
# above
LIMIT = 0.01
ONE_CHIP = {"chat": CHAT, "reasoning": REASONING}


def _run(name: str, fault=None, monkeypatch=None, control=False) -> dict:
    if fault is not None:
        monkeypatch.setattr(run, "new_driver", planted(run, fault))
    cell = tiny_cell(ONE_CHIP[name], dtype="float32", limit=LIMIT)
    return run.run_cell(cell, 777, 2.0, False, jax.devices()[:1],
                        t_process=time.perf_counter(), control=control)


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["check"]
    assert out["window"]["compiles"] == 0
    assert list(out["check"]) == ["mean_logit_gap", "requests_unanswered",
                                  "served_tokens_compared"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_broken_run_is_not_correct(name, fault, monkeypatch):
    out = _run(name, FAULTS[fault], monkeypatch)
    assert not out["correct"], out["check"]


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_control_run_is_not_correct(name):
    out = _run(name, control=True)
    assert out["window"]["control"] is True
    assert out["window"]["program_mean_logit_gap"] <= LIMIT
    assert not out["correct"], out["check"]
