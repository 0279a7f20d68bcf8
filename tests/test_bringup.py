"""What the chip bring-up (PR 21) established, held in place on the CPU.

These are the host-side halves of contracts that only matter on a machine
with a chip: a parent that imports the package must not claim the chip, the
compile cache lives where the environment says, a measurement path refuses
to run off the chip, and utilization is never computed against a made-up
peak. All fast and CPU-only.
"""
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_path, *, args=(), env=None, cwd=REPO_ROOT, timeout=120):
    penv = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT)
    penv.pop("JAX_COMPILATION_CACHE_DIR", None)
    penv.update(env or {})
    argv = ([sys.executable, code_or_path, *args]
            if os.path.exists(code_or_path)
            else [sys.executable, "-c", code_or_path])
    return subprocess.run(argv, capture_output=True, text=True, env=penv,
                          cwd=cwd, timeout=timeout)


def test_imports_leave_the_backend_uninitialised():
    """A chip belongs to one process: the launcher parent and the serving
    CLI import these and then start the process that needs the chip."""
    proc = _run(
        "import jax._src.xla_bridge as xb\n"
        "for m in ('paddle_tpu', 'paddle_tpu.distributed.launch',\n"
        "          'paddle_tpu.serving', 'paddle_tpu.compilecache'):\n"
        "    __import__(m)\n"
        "    assert not xb.backends_are_initialized(), m\n"
        "import paddle_tpu\n"
        "paddle_tpu.seed(7)\n"
        "assert not xb.backends_are_initialized(), 'seed'\n"
        "paddle_tpu.randn([2])\n"
        "assert xb.backends_are_initialized()\n"
    )
    assert proc.returncode == 0, proc.stderr


def test_cache_root_follows_the_environment(tmp_path, monkeypatch):
    from paddle_tpu import compilecache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compilecache.cache_root() == os.path.join(REPO_ROOT, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compilecache.cache_root() == str(tmp_path)


def test_enable_persistent_cache_sets_no_directory_over_the_environment(
        tmp_path):
    """Env set: jax has read the directory itself and code sets no other.
    Env unset: the fixed path in the checkout. Either way programs that
    compile fast are kept."""
    code = (
        "import jax\n"
        "from paddle_tpu.compilecache import enable_persistent_cache\n"
        "root = enable_persistent_cache()\n"
        "print(root)\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n"
    )
    set_ = _run(code, env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert set_.returncode == 0, set_.stderr
    assert set_.stdout.split() == [str(tmp_path), str(tmp_path), "0.0"]
    unset = _run(code)
    assert unset.returncode == 0, unset.stderr
    fixed = os.path.join(REPO_ROOT, ".jax_cache")
    assert unset.stdout.split() == [fixed, fixed, "0.0"]


def test_chip_smoke_refuses_the_cpu_before_building_anything():
    proc = _run(os.path.join(REPO_ROOT, "chip_smoke.py"))
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    lines = proc.stdout.strip().splitlines()
    assert lines and lines[-1].startswith("== device")  # and nothing after
    assert not any(line.startswith("{") for line in lines)


def test_chip_smoke_result_line_has_exactly_the_contract_keys(monkeypatch):
    """The driver refuses any other key set (an extra "claim" did it once)."""
    import json

    monkeypatch.syspath_prepend(REPO_ROOT)
    import chip_smoke

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert json.loads(chip_smoke.result_line(device)) == {
        "ok": True, "device": device}


def test_peaks_table_raises_on_unknown_device_kind():
    from paddle_tpu.core.device import DEVICE_PEAKS, device_peaks

    v5e = device_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.int8_ops, v5e.hbm_bytes_per_s) == (
        197e12, 393e12, 819e9)
    assert all(p.source for p in DEVICE_PEAKS.values())
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks("TPU v99")
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks()      # the CPU mesh has no row either


def test_place_raises_for_an_absent_device_type():
    from paddle_tpu.core.device import Place

    assert Place("cpu", 0).jax_device.platform == "cpu"
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        Place("tpu", 0).jax_device


def test_generated_ops_match_the_generator():
    from paddle_tpu.ops import gen

    py, pyi = gen.generate()
    for text, name in ((py, "_generated.py"), (pyi, "_generated.pyi")):
        with open(os.path.join(gen.HERE, name)) as f:
            assert f.read() == text, f"{name} is stale: run ops/gen.py"
