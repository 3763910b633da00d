"""What running on one chip requires of the program, checked on the CPU
with the backend steered inside each test:

* a chip belongs to one process, so the proving services refuse
  subprocess isolation on a TPU backend;
* both compile caches sit under ``$JAX_COMPILATION_CACHE_DIR`` when it
  is set, else at one fixed path inside the checkout;
* the executable cache keys on the device kind, so one chip
  generation never loads another's executables;
* ``chip_smoke.py`` finds no accelerator here, exits non-zero and
  prints no ``ok`` line.
"""
import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def on_tpu(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _prover_service(out_dir, isolation):
    from repro.launch.serve import ProverService
    return ProverService(None, out_dir=out_dir, isolation=isolation)


def _gateway(out_dir, isolation):
    from repro.launch.serve import ProvingGateway
    return ProvingGateway(out_dir, isolation=isolation)


@pytest.mark.parametrize("make", [_prover_service, _gateway],
                         ids=["service", "gateway"])
def test_subprocess_isolation_refused_on_tpu(on_tpu, tmp_path, make):
    with pytest.raises(ValueError, match="one process") as exc:
        make(str(tmp_path), "subprocess")
    assert "isolation='thread'" in str(exc.value)
    assert make(str(tmp_path), "thread").isolation == "thread"


@pytest.mark.parametrize("make", [_prover_service, _gateway],
                         ids=["service", "gateway"])
def test_subprocess_isolation_allowed_off_tpu(tmp_path, make):
    assert make(str(tmp_path), "subprocess").isolation == "subprocess"


@pytest.mark.parametrize("env_dir", [None, "set"])
def test_cache_root_placement(monkeypatch, tmp_path, env_dir):
    from repro.core import execache
    from repro.util import cache_root

    monkeypatch.delenv("ZKDL_EXEC_CACHE", raising=False)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    else:
        want = str(tmp_path / "cc")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert cache_root() == want
    assert execache.disk_root() == os.path.join(want, "zkdl-exec")
    assert os.path.dirname(execache.cache_dir()) == execache.disk_root()


def test_exec_cache_override_and_off(monkeypatch, tmp_path):
    from repro.core import execache

    monkeypatch.setenv("ZKDL_EXEC_CACHE", str(tmp_path))
    assert execache.disk_root() == str(tmp_path)
    monkeypatch.setenv("ZKDL_EXEC_CACHE", "off")
    assert execache.disk_root() is None and execache.cache_dir() is None


def test_exec_cache_keys_on_device_kind(monkeypatch):
    """An executable is keyed, on disk too, by the device kind it was
    compiled for: one built for another chip generation never loads."""
    import jax
    import jax.numpy as jnp
    from repro.core import execache

    x = jnp.zeros((4,), jnp.uint32)
    here = execache._key("f", (x,), {})
    assert jax.devices()[0].device_kind in here
    assert jax.devices()[0].device_kind in execache.cache_dir()
    monkeypatch.setattr(execache, "device_kind", lambda: "TPU v5 lite")
    chip = execache._key("f", (x,), {})
    assert "TPU v5 lite" in chip and chip != here
    assert os.path.basename(execache.cache_dir()).endswith(
        f"-TPU_v5_lite-v{execache._SCHEMA}")


def test_chip_smoke_refuses_without_tpu(capsys):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
