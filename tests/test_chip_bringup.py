"""Bring-up contracts: where the compile cache lives, how the native
library is named and rebuilt, and chip_smoke.py's device gate."""

import json
import logging
import os
import shutil
import subprocess
import sys

import flink_tpu
import flink_tpu.native as nat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRINT_CACHE_DIR = ("import flink_tpu, jax; "
                   "print(jax.config.jax_compilation_cache_dir)")


def _run(args, cwd, **env_overrides):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    env.update(env_overrides)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


# ---------------------------------------------------------------------
# compile cache placement

def test_compile_cache_from_outside_is_left_alone(tmp_path):
    outside = str(tmp_path / "given")
    p = _run(["-c", PRINT_CACHE_DIR], str(tmp_path),
             JAX_COMPILATION_CACHE_DIR=outside)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == outside


def test_compile_cache_default_is_one_path_in_the_checkout(tmp_path):
    other = tmp_path / "elsewhere"
    other.mkdir()
    got = set()
    for cwd in (str(tmp_path), str(other)):
        p = _run(["-c", PRINT_CACHE_DIR], cwd)
        assert p.returncode == 0, p.stderr
        got.add(p.stdout.strip())
    assert got == {os.path.join(REPO, ".jax_cache")}
    assert flink_tpu.COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")


def test_package_import_initialises_no_backend(tmp_path):
    p = _run(["-c", "import flink_tpu.streaming.datastream; "
                    "from jax._src import xla_bridge; "
                    "print(len(xla_bridge._backends))"], str(tmp_path))
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "0"


# ---------------------------------------------------------------------
# native artifact naming

def test_artifact_name_carries_source_command_and_cpu():
    base = nat.artifact_name(b"src", ("g++", "-O3"), "avx2 sse4_2")
    assert base == nat.artifact_name(b"src", ("g++", "-O3"), "avx2 sse4_2")
    assert base.startswith("libhost_runtime-") and base.endswith(".so")
    others = {nat.artifact_name(b"src ", ("g++", "-O3"), "avx2 sse4_2"),
              nat.artifact_name(b"src", ("g++", "-O2"), "avx2 sse4_2"),
              nat.artifact_name(b"src", ("g++", "-O3"), "avx2 avx512f")}
    assert len(others) == 3 and base not in others


def _fresh_loader(monkeypatch, build_dir):
    monkeypatch.setattr(nat, "_BUILD_DIR", str(build_dir))
    monkeypatch.setattr(nat, "_lib", None)
    monkeypatch.setattr(nat, "_lib_path", None)
    monkeypatch.setattr(nat, "_load_error", None)


def test_library_under_a_stale_name_is_not_loaded(tmp_path, monkeypatch):
    real = nat.library_path()
    assert real, nat.load_error()
    with open(nat._SRC, "rb") as f:
        src = f.read()
    # what a copy from a host with another CPU leaves behind
    stale = tmp_path / nat.artifact_name(src, nat._COMPILE, "avx512f zmm")
    stale.write_bytes(b"built for another cpu")
    built = []

    def build(out_path):
        built.append(out_path)
        shutil.copy(real, out_path)

    _fresh_loader(monkeypatch, tmp_path)
    monkeypatch.setattr(nat, "_build", build)
    assert nat.available()
    want = str(tmp_path / nat.artifact_name(src, nat._COMPILE,
                                            nat.cpu_feature_flags()))
    assert built == [want]
    assert nat.library_path() == want != str(stale)
    assert os.path.basename(real) == os.path.basename(want)


def test_failed_build_logs_compiler_stderr_once(tmp_path, monkeypatch,
                                                caplog):
    _fresh_loader(monkeypatch, tmp_path)
    monkeypatch.setattr(nat, "_COMPILE",
                        ("g++", "--no-such-flag-for-the-bring-up-test"))
    with caplog.at_level(logging.ERROR, logger=nat.log.name):
        assert not nat.available()
        assert not nat.available()
    errors = [r for r in caplog.records if r.levelno == logging.ERROR]
    assert len(errors) == 1
    assert "no-such-flag-for-the-bring-up-test" in errors[0].getMessage()
    assert "no-such-flag" in nat.load_error()
    assert os.listdir(tmp_path) == []


# ---------------------------------------------------------------------
# chip_smoke.py

def _result_line(stdout):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except ValueError:
        return None


def test_chip_smoke_refuses_a_cpu():
    p = _run(["chip_smoke.py"], REPO)
    assert p.returncode != 0
    out = _result_line(p.stdout)
    assert not (isinstance(out, dict) and "ok" in out), p.stdout[-500:]
    assert "no TPU: jax found cpu" in p.stdout


def test_chip_smoke_preflight_passes_every_leg():
    p = _run(["chip_smoke.py", "--cpu-preflight"], REPO)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "NOT a chip run" in p.stdout
    assert "FAILED" not in p.stdout
    out = _result_line(p.stdout)
    assert out["ok"] is True and out["preflight"] is True
    assert out["device"]["platform"] == "cpu"
    # conftest's 8 virtual devices reach the subprocess: the mesh leg ran
    assert "[6 mesh] ok" in p.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert _result_line(p.stdout) is None
