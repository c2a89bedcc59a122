"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and each
phase's checks pass at a small size.

The script itself only runs on a TPU with the compiled ``pallas`` backend.
Here its phase functions run at small sizes on the ``ref`` backend (the
reference comparison is then trivially exact; statuses, closed forms, the
fused-path checks and the served cache accounting are what is exercised)
and, for the batch phase, on the ``interpret`` backend.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import pytest

from repro.kernels import ops

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def backend():
    old = ops.backend()

    def use(name):
        ops.set_backend(name)

    yield use
    ops.set_backend(old)


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    old = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == old


def test_refuses_without_a_tpu(cs, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main([]) == 2
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


def test_a_raising_phase_fails_the_run(cs, capsys):
    results = []
    with cs.phase("broken", results):
        raise ValueError("boom")
    assert results == [False]
    assert '"ok": false' in capsys.readouterr().out


PHASES = {
    "batch": lambda cs, r: cs.phase_batch(r, batch=16, n_eval=20),
    "network": lambda cs, r: cs.phase_network(r, batch=8, features=130, hidden=32),
    "stiff": lambda cs, r: cs.phase_stiff(r, dict(batch=8, features=16), dict(batch=8)),
    "events": lambda cs, r: cs.phase_events(r, batch=16),
    "served": lambda cs, r: cs.phase_served(r, requests=48, max_batch=8,
                                            features=(2, 5), grids=(0, 8, 20)),
    "sharded": lambda cs, r: cs.phase_sharded(r, batch=6, n_eval=10,
                                              devices=jax.devices()[:1]),
    "served_multi": lambda cs, r: cs.phase_served_multi(
        r, requests=24, max_batch=4, features=(2, 3), grids=(0, 8),
        devices=jax.devices()[:1]),
}
CASES = [(name, "ref") for name in PHASES] + [("batch", "interpret")]


@pytest.mark.parametrize("name,kernels", CASES, ids=[f"{n}-{k}" for n, k in CASES])
def test_phase_passes_at_small_size(cs, backend, capsys, name, kernels):
    backend(kernels)
    results = []
    PHASES[name](cs, results)
    out = capsys.readouterr().out
    assert results and all(results), out
