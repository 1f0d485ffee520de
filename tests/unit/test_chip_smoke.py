"""`chip_smoke.py` refuses to pass anywhere but at full width on a TPU.

Each case runs the script as a child with ``JAX_PLATFORMS=cpu`` (which
never loads the TPU library) and holds it to the contract: a non-zero
exit code and no ``"ok": true`` line.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(script, args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # one CPU device, like one chip
    return subprocess.run([sys.executable, script, *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def _smoke_lines(stdout):
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


@pytest.mark.parametrize("case", ["no_tpu", "reduced", "script_alone"])
def test_chip_smoke_never_passes_without_the_chip(case, tmp_path):
    if case == "script_alone":
        # nothing of the repo beside it: the import of the program fails
        script = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
        r = _run(str(script), [], cwd=tmp_path)
    else:
        args = ["--reduced"] if case == "reduced" else []
        r = _run(SCRIPT, args, cwd=REPO)
    assert r.returncode != 0, r.stdout[-2000:]
    assert '"ok": true' not in r.stdout
    lines = _smoke_lines(r.stdout)
    if case == "no_tpu":
        # refused before anything ran
        assert r.returncode == 2 and lines == []
        assert "no TPU" in r.stderr
    if case == "reduced":
        # the rehearsal runs both phases to their end — and still is
        # not a pass
        assert r.returncode == 3, r.stderr[-2000:]
        phases = [l.get("smoke") for l in lines]
        # serving twice: the defaults (dense), then flash, both over
        # the paged pool (the only KV layout since PR 28)
        assert phases.count("train") == 1 and phases.count("serve") == 2
        assert phases[-2] == "done"
        assert lines[-1]["ok"] is False and lines[-1]["rehearsal"]
        serves = [l for l in lines if l.get("smoke") == "serve"]
        assert all(s["compile_counts"] == {"prefill": 1, "decode": 1}
                   for s in serves)
        assert all("vs_dense" in s for s in serves[1:])
