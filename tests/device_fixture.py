"""Multi-device CPU test fixture: run a callable in a SUBPROCESS with a
forced host-platform device count.

The jax device count is fixed at backend init
(``--xla_force_host_platform_device_count`` is read once), so a test
that needs a DIFFERENT count than conftest's 8 — a single-device
process to exercise the tp_degree device check, a pristine process to
prove a warm restart replays zero traces across process boundaries —
must re-init jax in a fresh interpreter. ``run_with_device_count``
spawns one, imports ``module:function`` from the tests directory, calls
it with JSON-round-tripped args, and returns its JSON-serializable
result.
"""
import json
import os
import subprocess
import sys

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_TESTS_DIR)

_BOOTSTRAP = """\
import json, sys, importlib
mod, fn = sys.argv[1].split(":")
f = getattr(importlib.import_module(mod), fn)
out = f(*json.loads(sys.argv[2]))
print("RESULT::" + json.dumps(out))
"""


def run_with_device_count(n, target, *args, timeout=600, env=None):
    """Run ``target`` ("module:function", importable from tests/) in a
    subprocess whose jax backend is CPU with ``n`` forced host devices.
    ``args`` and the return value must be JSON-serializable. Raises
    AssertionError with the child's output on any failure."""
    penv = dict(os.environ)
    penv.update(env or {})
    penv["JAX_PLATFORMS"] = "cpu"
    penv.setdefault("JAX_ENABLE_X64", "0")
    # XLA_FLAGS is REPLACED, not inherited: tests earlier in the suite
    # mutate the process env with backend-specific flags (e.g. the
    # TPU-style collective-combiner thresholds) that the child's CPU
    # backend rejects at init — and the fixture's whole point is a
    # deterministic device count regardless of suite ordering
    penv["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={int(n)}"
    )
    penv["PYTHONPATH"] = os.pathsep.join(
        p for p in (_REPO_ROOT, _TESTS_DIR,
                    penv.get("PYTHONPATH", "")) if p
    )
    proc = subprocess.run(
        [sys.executable, "-c", _BOOTSTRAP, target, json.dumps(list(args))],
        capture_output=True, text=True, timeout=timeout, env=penv,
        cwd=_TESTS_DIR,
    )
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT::"):
            return json.loads(line[len("RESULT::"):])
    raise AssertionError(
        f"no RESULT from {target} under {n} device(s) "
        f"(rc={proc.returncode})\n--- stdout ---\n{proc.stdout}\n"
        f"--- stderr ---\n{proc.stderr}"
    )
