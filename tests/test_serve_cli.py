"""The serving entry point and the compile-cache placement it shares with
``chip_smoke.py`` and ``benchmarks/run.py``."""

import os
from pathlib import Path

import jax

from repro.launch import compile_cache, serve

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_env_var_is_honoured_and_left_alone(monkeypatch,
                                                          tmp_path):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert updates == []                      # JAX reads the variable itself
    assert os.environ[compile_cache.ENV_VAR] == str(tmp_path)


def test_compile_cache_fallback_is_fixed_inside_checkout(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.configure_compile_cache()
    second = compile_cache.configure_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert Path(first).resolve().is_relative_to(REPO)
    assert updates == [("jax_compilation_cache_dir", first)] * 2


def test_serve_smoke_runs_to_completion(monkeypatch, tmp_path, capsys):
    """``python -m repro.launch.serve --smoke`` serves every request
    through the batched engine and exits 0."""
    # an already-imported JAX ignores the variable, so nothing is cached
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    built = []
    real = serve.BatchedLeoAMEngine

    def spy(*a, **kw):
        built.append(real(*a, **kw))
        return built[-1]

    monkeypatch.setattr(serve, "BatchedLeoAMEngine", spy)
    rc = serve.main(["--smoke", "--requests", "2", "--gen", "4"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert out.startswith("device [cpu:")
    assert len(built) == 1 and built[0].max_seqs == 2
    lines = [l for l in out.splitlines() if l.startswith("request ")]
    assert len(lines) == 2
    assert all(l.endswith("4 generated, error=None") for l in lines)
    assert "disk -> host" in out and "host -> device" in out
