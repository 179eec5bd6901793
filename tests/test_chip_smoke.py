"""chip_smoke.py where there is no chip, and the compile-cache rule.

The smoke's verdict belongs to the TPU: here it must fail, quickly at
full size (the server's own banner says "cpu") and after running every
phase at ``--tiny`` size — which is also what keeps its control flow
(children, requests, reference comparison, checkpoint read-back) from
rotting between chip runs.
"""
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))   # chip_smoke.py is a script at the root


def _smoke(tmp_path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"),
         "--out", str(tmp_path / "out"), *args],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=str(tmp_path))


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    proc = _smoke(tmp_path)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr, proc.stderr[-2000:]


def test_chip_smoke_tiny_rehearsal_runs_every_phase_and_is_not_ok(tmp_path):
    proc = _smoke(tmp_path, "--tiny")
    assert "all phases passed" in proc.stdout, (
        proc.stdout[-3000:] + proc.stderr[-3000:])
    for phase in ("serve: 5 requests through the LB", "1 prefix hit",
                  "positions clear the margin",
                  "serve-ref: engine driven in the child (paged True, "
                  "960 prompt tokens of the repeated request from the "
                  "prefix cache", "checkpoint of step 24 read back"):
        assert phase in proc.stdout
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "not ok: a --tiny rehearsal" in proc.stderr


@pytest.mark.parametrize("tiny,platform,ok", [
    (False, "tpu", True),
    (False, "cpu", False),
    (True, "cpu", False),
    (True, "tpu", False),   # tiny on a chip is still a toy-width run
])
def test_only_a_full_width_tpu_run_earns_the_ok_line(tiny, platform, ok):
    import chip_smoke
    device = {"platform": platform, "kind": "x", "count": 1}
    assert (chip_smoke.verdict_refused(tiny, device) == "") == ok


_PRINT_CACHE = (
    "import jax\n"
    "from skypilot_tpu.utils import compile_cache\n"
    "print(compile_cache.enable())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def _cache_lines(cwd, **env_changes):
    env = dict(os.environ, PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_changes)
    out = subprocess.run([sys.executable, "-c", _PRINT_CACHE],
                         capture_output=True, text=True, timeout=120,
                         env=env, cwd=str(cwd), check=True).stdout
    return out.strip().splitlines()


def test_cache_helper_honours_the_variable(tmp_path):
    """Set: nothing sets a directory in code; JAX read it itself."""
    given = str(tmp_path / "given")
    assert _cache_lines(tmp_path, JAX_COMPILATION_CACHE_DIR=given) == \
        [given, given]


def test_cache_helper_falls_back_to_one_path_in_the_checkout(tmp_path):
    """Unset: two processes in two working directories agree on the
    one git-ignored path inside the checkout."""
    (tmp_path / "elsewhere").mkdir()
    first = _cache_lines(tmp_path)
    second = _cache_lines(tmp_path / "elsewhere")
    assert first == second == [str(REPO / ".jax_cache")] * 2
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
