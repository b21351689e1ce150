"""chip_smoke.py's CPU rehearsal, its refusal to run off TPU without
``--reduced``, and where the entry points put the compile cache."""

import importlib.util
import json
from pathlib import Path

import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_reduced_rehearsal_passes(chip_smoke, capsys):
    assert chip_smoke.main(["--reduced"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
    assert any(line.startswith("[kernel]") for line in lines)
    assert any(line.startswith("[cross]") for line in lines)


def test_refuses_cpu_without_reduced(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_dir_env_set_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() is None


def test_compile_cache_dir_unset_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == str(ROOT / ".jax_cache")
    assert compile_cache.compile_cache_dir() == first
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
