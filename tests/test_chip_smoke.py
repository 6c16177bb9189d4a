"""chip_smoke.py on the CPU: its serving checks at the smoke size, and its
refusal to run without a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.configs import get_smoke_config
from repro.models import Model
from repro.serving import ServeConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
SCFG = ServeConfig(max_batch=4, max_len=64, prefill_bucket=16,
                   max_new_tokens=4)


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


chip_smoke = _load_chip_smoke()


@pytest.fixture(scope="module")
def tiny():
    model = Model(get_smoke_config("qwen2-0.5b").with_(remat=False))
    return model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("backend", ("fiber", "thread"))
def test_smoke_serves_and_checks(tiny, backend, monkeypatch):
    model, params = tiny
    monkeypatch.setattr(chip_smoke, "BACKENDS", (backend,))
    monkeypatch.setattr(chip_smoke, "N_REQUESTS", 6)
    (rep,) = chip_smoke.run_smoke(model, params, SCFG)
    assert rep.backend == backend
    assert (rep.sent, rep.answered, rep.failed) == (6, 6, 0)
    assert all(len(t) == SCFG.max_new_tokens for t in rep.tokens)
    assert rep.driver_error is None


def test_check_report_rejects_a_wrong_token(tiny, monkeypatch):
    model, params = tiny
    monkeypatch.setattr(chip_smoke, "BACKENDS", ("fiber",))
    monkeypatch.setattr(chip_smoke, "N_REQUESTS", 2)
    (rep,) = chip_smoke.run_smoke(model, params, SCFG)
    expected = [list(t) for t in rep.tokens]
    device = jax.devices()[0]
    chip_smoke.check_report(rep, expected, SCFG.max_new_tokens, device)
    expected[1][-1] += 1
    with pytest.raises(chip_smoke.SmokeFailure, match="request 1"):
        chip_smoke.check_report(rep, expected, SCFG.max_new_tokens, device)


def test_prompts_are_distinct_and_varied():
    texts = chip_smoke.make_prompts(16, 128)
    assert len(set(texts)) == 16
    assert len({len(t) for t in texts}) > 8
    assert all(8 <= len(t) <= 128 for t in texts)
    assert texts == chip_smoke.make_prompts(16, 128)


def test_main_refuses_cpu(monkeypatch, capsys):
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: jax.local_devices(backend="cpu"))
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Without the rest of the repo the script exits non-zero, no result."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
