"""The chip smoke script's own logic, on the CPU at the reduced Granite size.

``chip_smoke.main`` takes the config, the request plan and the required
platform as keyword arguments so these tests can run its checks here; the
command line always runs the full-size model and requires a TPU.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs.base import get_config  # noqa: E402

SMALL = chip_smoke.Plan(prompt_lens=(30, 20, 26, 33, 12), new_tokens=6,
                        max_batch=2, chunk_len=8, page_size=4, num_pages=64,
                        max_cache=64, warmup_len=10)


def _reduced():
    # 8 padded experts so the four-device mesh holds 2 each
    return get_config(chip_smoke.ARCH).reduced().replace(
        num_experts=6, num_experts_padded=8)


def _last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_smoke_checks_pass_on_reduced_config(capsys):
    assert chip_smoke.main([], cfg=_reduced(), plan=SMALL,
                           platform="cpu") == 0
    out = capsys.readouterr().out
    assert "first_token_logits rel_l2" in out
    assert _last_json(out) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}


def test_smoke_fails_on_wrong_logits(monkeypatch, capsys):
    """The logit comparison is live: a reference shifted by one position
    (the prompt minus its last token) must fail the run."""
    class ShiftedModel:
        def __init__(self, model):
            self._m = model

        def __getattr__(self, name):
            return getattr(self._m, name)

        def forward(self, params, batch):
            return self._m.forward(params, {"tokens": batch["tokens"][:, :-1]})

    class Engine(chip_smoke._LogitProbeEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.model = ShiftedModel(self.model)

    monkeypatch.setattr(chip_smoke, "_LogitProbeEngine", Engine)
    with pytest.raises(chip_smoke.SmokeFailure, match="differ from Model"):
        chip_smoke.main([], cfg=_reduced(), plan=SMALL, platform="cpu")
    assert '"ok"' not in capsys.readouterr().out


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_exits_nonzero_without_tpu():
    proc = _run([os.path.join(ROOT, "chip_smoke.py")], cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a tpu device" in proc.stderr


def test_smoke_exits_nonzero_outside_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _run([str(tmp_path / "chip_smoke.py")], cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


_FOUR = """
import sys
sys.path.insert(0, {root!r})
sys.path.insert(0, {here!r})
import chip_smoke
from test_chip_smoke import SMALL, _reduced
sys.exit(chip_smoke.main(["--four-chip"], cfg=_reduced(), plan=SMALL,
                         platform="cpu"))
"""


def test_four_chip_phase_on_virtual_devices():
    """--four-chip on four host devices: the mesh engine's greedy tokens
    equal the one-device engine's."""
    proc = _run(["-c", _FOUR.format(root=ROOT, here=HERE)], cwd=ROOT,
                env_extra={
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                    "PYTHONPATH": os.path.join(ROOT, "src")})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "greedy tokens equal over 5 requests" in proc.stdout
    assert _last_json(proc.stdout)["device"]["count"] == 4
