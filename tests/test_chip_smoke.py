"""chip_smoke.py refuses every way of not running on the chip.

The script itself only runs on a TPU; these tests hold it to its contract
on the CPU: it exits non-zero and prints no result line without an
accelerator or without the repository beside it, and its service check
refuses a fault that the degradation ladder absorbed.  They also pin the
compile-cache helper that the script and ``serve_truss`` share.
"""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load_script():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(str(cwd), "cache"))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(out) -> bool:
    lines = out.stdout.strip().splitlines()
    return out.returncode != 0 and not (lines and lines[-1].startswith("{"))


def test_fails_without_an_accelerator(tmp_path):
    out = _run(tmp_path, SCRIPT)
    assert _no_result(out), out.stdout + out.stderr
    assert "no TPU" in out.stderr


def test_fails_without_the_repository(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run(tmp_path, str(lone))
    assert _no_result(out), out.stdout + out.stderr
    assert "not beside this script" in out.stderr


def test_service_check_refuses_an_absorbed_peel_fault():
    """A generation that failed its engine and was recovered by the
    recompute fallback still fails the smoke."""
    from repro.data.synthetic import powerlaw_graph
    from repro.faults import PeelChaos
    from repro.obs import metrics
    from repro.service import TrussService

    cs = _load_script()
    edges = powerlaw_graph(60, 3, seed=0)
    present = {tuple(map(int, e)) for e in edges}
    ups = [(1, a, b) for a in range(60) for b in range(a + 1, 60)
           if (a, b) not in present][:20]
    metrics.REGISTRY.reset()
    svc = TrussService(60, edges, flush_every=len(ups), strategy="fused",
                       chaos=PeelChaos(dispatch_gens=[1]))
    svc.submit_many(ups)
    svc.flush()
    assert svc.gen == 1 and svc.stats()["degraded"] is None
    with pytest.raises(cs.SmokeFailure, match="truss_peel_fault_total"):
        cs.check_service(svc, "test")
    metrics.REGISTRY.reset()
    cs.check_service(svc, "test")  # the same service, counters clean: ok


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.configure_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_leaves_the_environment_in_charge(monkeypatch,
                                                        tmp_path):
    from repro.launch import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was  # nothing set


def test_kernel_dispatch_follows_the_one_backend_probe(monkeypatch):
    """``ops.on_tpu`` steers every kernel entry point: off a TPU the peel
    wave takes the reference; patched on, it dispatches the compiled
    Mosaic kernel, which the CPU backend cannot run."""
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, 2**32, size=(2, 40, 3),
                                    dtype=np.uint32))
    alive = jnp.asarray(rng.random(40) < 0.8)
    assert not ops.on_tpu() and ops._interpret()
    for got, exp in zip(ops.peel_wave(rows[0], rows[1], alive, 5),
                        ref.peel_wave_ref(rows[0], rows[1], alive, 5)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert not ops._interpret()
    with pytest.raises(ValueError, match="Only interpret mode"):
        ops.peel_wave(rows[0], rows[1], alive, 5)
