"""Contract tests for ``__graft_entry__``, the CPU sim-mesh check.

``dryrun_multichip(N)`` runs on a box that may have fewer than N real
devices, on virtual CPU devices it provisions itself, always inside the
calling process. These tests pin that self-provisioning contract.
"""

import os
import pytest
import subprocess
import sys

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import __graft_entry__ as graft  # noqa: E402

#: multi-process spawns / full-model training sweeps: the suite's
#: heavyweights (measured r05 durations); `make test-fast` skips them
pytestmark = pytest.mark.slow


def test_entry_is_jittable():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (128, 10)
    assert np.isfinite(np.asarray(out)).all()


def test_devices_for_provisions_virtual_devices():
    devs = graft._devices_for(8)
    assert devs is not None and len(devs) == 8


def test_devices_for_provisions_in_process():
    """The non-trivial branch: jax already imported, backends NOT yet
    initialized, no env help — the jax_num_cpu_devices config route must
    provision."""
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "_TFT_DRYRUN_CHILD")
    }
    env["JAX_PLATFORMS"] = "cpu"
    code = (
        "import jax\n"  # preimport without initializing backends
        "import __graft_entry__ as g\n"
        "devs = g._devices_for(8)\n"
        "assert devs is not None and len(devs) == 8, devs\n"
        "print('in-process OK')\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "in-process OK" in res.stdout


def test_dryrun_multichip_in_process():
    # conftest provisions 8 virtual CPU devices; exercise the full path.
    graft.dryrun_multichip(4)


def test_dryrun_multichip_subprocess_single_device():
    """CI's invocation shape: fresh interpreter, no env help, only one
    device visible — must still exit 0, and say what it ran on."""
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    # pin the interpreter to one CPU device so provisioning must do the work
    env["JAX_PLATFORMS"] = "cpu"
    code = "import __graft_entry__ as g; g.dryrun_multichip(8)"
    res = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "platform cpu" in res.stdout


def test_dryrun_refuses_once_backends_are_too_small():
    """No re-exec behind the caller's back: a process whose backends are
    already up with too few devices gets an error that says what to do."""
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env["JAX_PLATFORMS"] = "cpu"
    code = (
        "import jax\n"
        "jax.devices()\n"  # one CPU device, pinned
        "import __graft_entry__ as g\n"
        "try:\n"
        "    g.dryrun_multichip(8)\n"
        "except RuntimeError as e:\n"
        "    assert 'fresh process' in str(e), e\n"
        "    print('refused')\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "refused" in res.stdout
