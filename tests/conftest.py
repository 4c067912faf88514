"""Suite-wide conftest.

Provides a minimal ``hypothesis`` stand-in when the real package is absent
(offline CI containers can't pip install); see repro._compat.hypothesis_stub.

Also hosts the shared subprocess harness for the mesh/driver tests: they
spawn fresh interpreters (each sets its own fake-device count before jax
initializes), rooted at the repo checkout so ``PYTHONPATH=src`` resolves on
any machine, not just the original dev box.
"""
import pathlib
import subprocess
import sys

try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    from repro._compat import hypothesis_stub

    hypothesis_stub.install()


REPO_ROOT = str(pathlib.Path(__file__).resolve().parent.parent)
# JAX_PLATFORMS keeps children on the CPU: the package picks no platform.
SUBPROC_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}


def run_prog(prog: str, timeout: int = 560) -> str:
    """Run ``python -c prog`` from the repo root; assert success."""
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env=dict(SUBPROC_ENV), cwd=REPO_ROOT, timeout=timeout,
    )
    assert out.returncode == 0, (out.stderr[-3000:], out.stdout[-500:])
    return out.stdout
