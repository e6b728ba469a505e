"""``chip_smoke.py``'s phases on the CPU at smoke size.

The script has no CPU mode; these tests call its phase functions with the
reduced ``stablelm`` preset and small engines, so its checks are guarded
without a chip, and show that ``main()`` refuses a non-TPU backend."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

import repro.configs as C

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402

SMALL = dict(max_slots=4, max_len=128, buckets=(16, 32, 64))
MAX_NEW = 6


def _prompts(cfg):
    return cs.make_prompts(cfg.vocab, n=6, lens=(4, 60))


@pytest.mark.timeout(300)
def test_one_chip_phases_at_smoke_size(capsys):
    cfg = C.get(cs.ARCH, smoke=True)
    cs.one_chip(jax.devices()[0], cfg, _prompts(cfg), max_new=MAX_NEW, **SMALL)
    out = capsys.readouterr().out
    assert "tokens identical to the served run" in out
    assert "builds_on_thread=0" in out
    assert "decode compiled 1x" in out


@pytest.mark.timeout(300)
def test_one_chip_catches_diverging_tokens(monkeypatch):
    """The served-vs-direct comparison is live: a direct run that differs
    by one token fails the phase."""
    cfg = C.get(cs.ARCH, smoke=True)
    real = cs.run_direct

    def off_by_one(*args, **kw):
        tokens = real(*args, **kw)
        tokens[0][-1] = (tokens[0][-1] + 1) % cfg.vocab
        return tokens

    monkeypatch.setattr(cs, "run_direct", off_by_one)
    with pytest.raises(cs.SmokeFailure, match="differ"):
        cs.one_chip(jax.devices()[0], cfg, _prompts(cfg), max_new=MAX_NEW,
                    **SMALL)


@pytest.mark.timeout(120)
def test_core_phase_nimble_matches_eager():
    assert cs.core_phase() <= cs.CORE_ATOL


@pytest.mark.timeout(300)
def test_four_replicas_on_host_devices():
    """The ``--chips 4`` path on four XLA host devices (a child process:
    the device count is fixed when JAX starts)."""
    code = textwrap.dedent(f"""
        import jax, chip_smoke as cs, repro.configs as C
        cfg = C.get(cs.ARCH, smoke=True)
        devices = jax.devices()
        assert len(devices) == 4, devices
        cs.four_chips(devices, cfg, cs.make_prompts(cfg.vocab, n=6, lens=(4, 60)),
                      max_new={MAX_NEW}, **{SMALL!r})
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=280)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "every replica's tokens identical to the one-chip run" in out.stdout


def test_placement_check_catches_wrong_device():
    cfg = C.get(cs.ARCH, smoke=True)
    engine = cs.build_engine(cfg, cs.init_params(cfg), None, **SMALL)
    cs.check_placement("engine", engine, jax.devices()[0])
    with pytest.raises(cs.SmokeFailure, match="expected"):
        cs.check_placement("engine", engine, object())


def test_token_check_catches_out_of_range():
    cs.check_tokens("ok", [[0, 511]], vocab=512, max_new=2)
    with pytest.raises(cs.SmokeFailure, match="outside"):
        cs.check_tokens("bad", [[0, 512]], vocab=512, max_new=2)
    with pytest.raises(cs.SmokeFailure, match="expected 2"):
        cs.check_tokens("short", [[0]], vocab=512, max_new=2)


def test_main_refuses_a_cpu_backend():
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
