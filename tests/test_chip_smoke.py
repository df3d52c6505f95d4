"""chip_smoke.py on the CPU: the dry run passes, the real run refuses to run
without a chip, and init() puts JAX's compile cache where it is told to.

Each case is its own process: the smoke boots a cluster and a REST server,
and the cache directory is fixed at a process's first compile."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _run(args, env_extra, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    env.update(env_extra)
    return subprocess.run([sys.executable, *args], cwd=str(REPO), env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_cpu_dry_run_passes_every_phase(tmp_path):
    p = _run(["chip_smoke.py", "--cpu-dry-run"],
             {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")})
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "platform=cpu DRY RUN" in p.stdout
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["dry_run"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= 1
    # all four prediction sizes answered 200 and matched model.predict
    assert p.stdout.count("rows: HTTP 200, finite") == 4
    # the smoke set no cache directory of its own: it used the one given
    assert f"compile_cache={tmp_path / 'cc'}" in p.stdout


def test_without_a_chip_it_fails_and_prints_no_result(tmp_path):
    p = _run(["chip_smoke.py"],
             {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")})
    assert p.returncode != 0
    assert "chip_smoke result" not in p.stdout
    assert '"ok"' not in p.stdout
    assert "not a TPU" in p.stderr


_CACHE_DIR_PROBE = (
    "import jax, h2o3_tpu; h2o3_tpu.init(); "
    "print('CACHE_DIR=' + str(jax.config.jax_compilation_cache_dir))")


def _cache_dir_after_init(env_extra):
    p = _run(["-c", _CACHE_DIR_PROBE], env_extra, timeout=120)
    assert p.returncode == 0, p.stderr[-3000:]
    return [ln for ln in p.stdout.splitlines()
            if ln.startswith("CACHE_DIR=")][-1][len("CACHE_DIR="):]


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    want = str(tmp_path / "placed_from_outside")
    assert _cache_dir_after_init({"JAX_COMPILATION_CACHE_DIR": want}) == want


def test_compile_cache_defaults_to_the_checkout():
    assert _cache_dir_after_init({}) == str(REPO / ".jax_cache")
