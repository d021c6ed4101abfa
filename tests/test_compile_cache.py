"""The entry points' compile-cache helper: JAX's own variable wins, else a
fixed directory inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro.runtime import compile_cache


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_enable_compile_cache(env_dir, monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)

    got = compile_cache.enable_compile_cache()

    if env_dir is None:
        checkout = Path(__file__).resolve().parents[1]
        assert got == str(checkout / ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", got)]
    else:
        assert got == env_dir
        assert updates == []  # JAX reads the variable itself
