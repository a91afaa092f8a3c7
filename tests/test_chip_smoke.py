"""chip_smoke.py on the CPU: the device check refuses the CPU, and every
phase runs at the smoke preset with the test steering the platform."""

from __future__ import annotations

import functools
import importlib.util
import json
import os

import jax
import pytest

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_fwd)

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chip_smoke.py")

#: What a TPU v5e reports (`jax.devices()[0]`).
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_refuses_cpu(chip_smoke, capsys, monkeypatch):
    monkeypatch.setattr(chip_smoke, "enable_compile_cache", lambda: "")
    ident = chip_smoke.device_identity()
    assert ident["platform"] == "cpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.require_tpu(ident)
    assert "no TPU" in str(e.value.code)
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert '"ok"' not in out


def _interpreted_kernel(args):
    # The CPU cannot compile the Mosaic kernel: run it interpreted.  The
    # chip lowering's tpu_custom_call is checked by test_tpu_compile.py
    # on a described v5e; here only its numerics are exercised.
    return (functools.partial(decode_attention_fwd, interpret=True),
            "tpu_custom_call")


def test_phases_at_smoke_preset(chip_smoke, capsys, monkeypatch, tmp_path):
    # The test process keeps the persistent cache off; report an empty one.
    monkeypatch.setattr(chip_smoke, "enable_compile_cache",
                        lambda: str(tmp_path))
    monkeypatch.setattr(chip_smoke, "device_identity", lambda: dict(V5E))
    monkeypatch.setattr(chip_smoke, "compile_decode_kernel",
                        _interpreted_kernel)
    monkeypatch.setattr(chip_smoke, "PRESET", "smoke")
    chip_smoke.main()
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[-1] == ('{"ok": true, "device": {"platform": "tpu", '
                         '"kind": "TPU v5 lite", "count": 1}}')
    assert json.loads(lines[-1]) == {"ok": True, "device": V5E}
    text = "\n".join(lines)
    for phase in ("(a)", "(b)", "(c)", "(d)", "(e)", "(f)"):
        assert phase in text
    assert "16 requests served, every one with its budget" in text
    assert jax.devices()[0].platform == "cpu"


def test_compile_cache_placement(monkeypatch):
    """The environment's directory wins and the code then sets none;
    otherwise the cache sits at one fixed path inside the checkout."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    default = os.path.join(repo, ".jax_cache")
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv(compile_cache.ENV_VAR)
        assert compile_cache.enable_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        cc.reset_cache()
