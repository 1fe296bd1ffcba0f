"""Shared pytest fixtures: the many-fake-device subprocess harness.

A single pytest process must keep its single CPU device (setting
``xla_force_host_platform_device_count`` globally would leak into every
other test), so multi-device tests run their body in a *subprocess* whose
XLA_FLAGS force N fake host devices.  ``fake_devices`` packages that
pattern once: the child snippet gets a ``publish(obj)`` helper whose
argument is pickled back to the parent, so tests assert on structured
results instead of grepping stdout.

``device_grid`` parametrizes a test over pod-ish grid sizes; anything past
8 devices is ``@slow``-marked (compile times grow superlinearly with the
fake-device count) and excluded from the fast CI tier's ``-m "not slow"``.

``CPU_DOT_FLAGS`` keeps the CPU backend's matmuls on one code path.  By
default XLA:CPU hands some dot shapes to YNNPACK, whose summation order
differs from its own emitter's, so the same row can score a last-ulp
differently inside differently-shaped tiles.  The layout- and tile-parity
tests compare results bit for bit, so every test process (this one and
the fake-device children) runs with YNNPACK fusion off.
"""
import os
import pickle
import subprocess
import sys
import tempfile

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CPU_DOT_FLAGS = "--xla_cpu_experimental_ynn_fusion_type="
# Read when the CPU backend starts, which is after this module is imported.
os.environ["XLA_FLAGS"] = " ".join(
    filter(None, [os.environ.get("XLA_FLAGS"), CPU_DOT_FLAGS])
)

_PRELUDE = """\
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count={n} {cpu_dot_flags}"
)
import pickle as _pickle


def publish(obj):
    with open({path!r}, "wb") as _f:
        _pickle.dump(obj, _f)


"""


class FakeDeviceRunner:
    """Run a source snippet under N fake XLA host devices.

    Returns whatever the snippet ``publish()``-ed (None if it never
    called it).  A non-zero child exit raises with the child's stdout and
    stderr attached, so in-child ``assert`` failures read like local ones.
    """

    def __call__(self, source: str, n: int = 8, timeout: float = 600.0):
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        env.pop("XLA_FLAGS", None)
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "result.pkl")
            script = _PRELUDE.format(
                n=n, path=path, cpu_dot_flags=CPU_DOT_FLAGS
            ) + source
            out = subprocess.run(
                [sys.executable, "-c", script], env=env,
                capture_output=True, text=True, timeout=timeout,
                cwd=REPO_ROOT,
            )
            if out.returncode != 0:
                raise AssertionError(
                    f"fake-device child (n={n}) failed:\n"
                    f"--- stdout ---\n{out.stdout}\n"
                    f"--- stderr ---\n{out.stderr}"
                )
            if os.path.exists(path):
                with open(path, "rb") as f:
                    return pickle.load(f)
            return None


@pytest.fixture(scope="session")
def fake_devices():
    return FakeDeviceRunner()


@pytest.fixture(params=[
    8,
    pytest.param(16, marks=pytest.mark.slow),
    pytest.param(48, marks=pytest.mark.slow),
])
def device_grid(request):
    """Fake-device grid sizes: 8 in the fast tier, 16/48 behind @slow."""
    return request.param
