import os
import sys

# src layout import without install
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest


@pytest.fixture(scope="session")
def rng():
    import numpy as np
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _tracer_leak_guard():
    """Fail any test that leaves an *enabled* tracer armed: step.trace is
    no-op by default, and a leaked global arm would silently tax every test
    (and benchmark) that runs after it."""
    yield
    telemetry = sys.modules.get("repro.core.telemetry")
    if telemetry is None:
        return
    leaked = telemetry.armed_count()
    if leaked:
        telemetry.reset()
        pytest.fail(f"test leaked {leaked} enabled tracer(s): disable() or "
                    "reset() tracers you arm (Session(trace=True) tracers "
                    "included) before the test returns")


@pytest.fixture(autouse=True)
def _checker_leak_guard():
    """Same contract for step.check: a leaked armed checker would tax (and
    potentially fail, via strict lint) every later test."""
    yield
    stepcheck = sys.modules.get("repro.check.checker")
    if stepcheck is None:
        return
    leaked = stepcheck.armed_count()
    if leaked:
        stepcheck.reset()
        pytest.fail(f"test leaked {leaked} enabled checker(s): disable() or "
                    "reset() checkers you arm (Session(check=True) checkers "
                    "included) before the test returns")


def run_subprocess_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run a code snippet in a fresh process with a forced host device count.

    Multi-device SPMD tests must NOT set xla_force_host_platform_device_count
    in this process (smoke tests see 1 device), so they shell out.
    """
    import subprocess
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env, timeout=timeout,
                          capture_output=True, text=True)
    assert proc.returncode == 0, f"subprocess failed:\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


def unfused_reduce(mat, k: int, block: int):
    """Oracle for the accumulator's pairs reduce: one blocked top-k sparsify
    per row of the stacked (N, V) round, then one scatter-add of the
    concatenated pairs.  Returns ``(sum, pair_counts, wire_elements)``, the
    wire being ``Σ 2·pairs + V`` as the accumulator accounts it."""
    import jax.numpy as jnp
    from repro.core.sparse import blocked_topk_sparsify, densify
    v = mat.shape[1]
    pairs = [blocked_topk_sparsify(row, k, block) for row in mat]
    total = densify(jnp.concatenate([p.idx for p in pairs]),
                    jnp.concatenate([p.vals for p in pairs]), v)
    return (total, [p.num_pairs for p in pairs],
            sum(p.wire_elements for p in pairs) + v)
