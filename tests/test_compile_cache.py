"""Placement of the persistent compile cache
(ceph_tpu/common/compile_cache.py): an operator's
JAX_COMPILATION_CACHE_DIR wins untouched; otherwise every process of
a checkout resolves to the same fixed directory inside it."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json, jax
from ceph_tpu.common.compile_cache import CACHE_DIR, enable_compile_cache
before = jax.config.jax_compilation_cache_dir
got = enable_compile_cache()
print(json.dumps({"got": got, "before": before, "fixed": str(CACHE_DIR),
                  "after": jax.config.jax_compilation_cache_dir}))
"""


def _resolve(**env_over) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_over)
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=120,
                       cwd="/")
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_operator_placement_is_left_alone(tmp_path):
    res = _resolve(JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert res["got"] == str(tmp_path)
    # jax's own reading of the variable, before and after: untouched
    assert res["before"] == res["after"] == str(tmp_path)
    assert res["fixed"] != str(tmp_path)


def test_unset_resolves_to_one_directory_inside_the_checkout():
    first, second = _resolve(), _resolve()
    assert first["before"] is None
    assert first["got"] == second["got"] == first["fixed"]
    assert first["after"] == first["got"]
    assert first["got"].startswith(REPO + os.sep)
    # no temp name, pid or timestamp: the path is the same string in
    # every process of this checkout
    assert first["got"] == os.path.join(REPO, ".jax_cache")
