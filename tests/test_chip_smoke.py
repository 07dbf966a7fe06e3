"""``chip_smoke.py``'s phases rehearsed on the CPU at 2^14 keys.

The script itself refuses to report success off a TPU; its phases are plain
functions of a key count, so the same code paths (pipeline -> dispatch
tiers, the device plane under both exchanges) are checked here against the
searchsorted oracle with the Pallas kernel interpreted.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
N_KEYS = 1 << 14


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


def _cpu_env(**extra):
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu", "HOME": os.environ.get("HOME", "/tmp")}
    env.update(extra)
    return env


def test_data_phase_is_sorted_f32_weblogs():
    smoke.data_phase(N_KEYS)
    keys = smoke.dataset(N_KEYS)
    assert keys.size == N_KEYS
    assert (keys[1:] >= keys[:-1]).all()
    assert (keys.astype("float32").astype("float64") == keys).all()


def test_served_phase_reaches_every_tier_and_matches_oracle(capsys):
    smoke.served_phase(N_KEYS)
    out = capsys.readouterr().out
    for backend in ("small=numpy", "medium=xla-bisect", "large=pallas"):
        assert backend in out


def test_device_plane_phase_matches_oracle():
    smoke.device_plane_phase(N_KEYS)


def test_four_device_phase_under_both_exchanges():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke as s; "
            "s.device_plane_phase(%d, device_count=4, "
            "exchanges=('allgather', 'a2a'))" % (str(ROOT), N_KEYS))
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT, env=_cpu_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "exchange=allgather" in res.stdout
    assert "exchange=a2a" in res.stdout


def test_main_refuses_a_non_tpu_platform(capsys):
    assert smoke.main(["--keys", str(N_KEYS)]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=300,
                         env=_cpu_env(PYTHONPATH=""))
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
