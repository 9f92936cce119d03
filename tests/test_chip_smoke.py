"""``chip_smoke.py``: the gate refuses a machine without a TPU, and the
CPU rehearsal drives every phase to a passing end.

The script is the quickest proof that the system starts on the chip
(ROADMAP, Open items). Tier-1 cannot run it for real; it can pin the two
properties the sandbox shows: without an accelerator the script exits
non-zero before phase 1 and names the platform it found, and
``--rehearse-cpu`` — the same phases at toy sizes, kernels interpreted —
exits 0 with every line stamped as a rehearsal.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(*args, timeout):
    # cwd elsewhere: the script must find the package from its own
    # location, and must leave its output directory beside itself
    return subprocess.run(
        [sys.executable, SCRIPT, *args],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(REPO),
        capture_output=True, text=True, timeout=timeout,
    )


def test_gate_refuses_a_machine_without_a_tpu():
    out = _run(timeout=120)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    # the gate line and nothing after it: no phase ran, no result printed
    assert [l.get("phase") for l in lines] == ["gate"]
    assert not any("ok" in l for l in lines)


def test_cpu_rehearsal_runs_every_phase():
    out = _run("--rehearse-cpu", timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    assert all(
        l.get("platform") == "cpu" and l.get("rehearsal") is True
        for l in lines
    ), "every line of a rehearsal is stamped"
    assert lines[-1]["ok"] is True
    assert lines[-1]["device"]["platform"] == "cpu"
    ended = {l["phase"] for l in lines if l.get("event") == "end"}
    assert ended == {
        "frame", "serve", "kernels", "pool_layout", "pool_layout.long",
        "four_chips.dp", "four_chips.tp",
        "four_chips.fleet", "four_chips.ring", "float64",
    }
    checks = [l for l in lines if "check" in l]
    assert len(checks) >= 40 and all(c["ok"] for c in checks)
