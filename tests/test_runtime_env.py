"""Runtime environments: working_dir, py_modules, pip venvs, env_vars.

Counterpart of the reference's `test_runtime_env*.py` suites over
`_private/runtime_env/` (working_dir.py, pip.py, uri_cache.py): the node
materializes the environment into a content-addressed cache before the
worker execs, so tasks/actors see packages and files the driver doesn't.
"""

import base64
import hashlib
import os
import zipfile

import pytest

import ray_tpu
from ray_tpu.exceptions import RuntimeEnvSetupError


def _make_wheel(tmp_path, name="rttestpkg", version="1.0",
                body=b"MAGIC = 12345\n"):
    """Craft a minimal pure-python wheel offline (a .whl is just a zip
    with dist-info metadata) so pip can install it with zero egress."""
    wheel_path = str(tmp_path / f"{name}-{version}-py3-none-any.whl")
    records = []

    def add(zf, arcname, data):
        zf.writestr(arcname, data)
        digest = base64.urlsafe_b64encode(
            hashlib.sha256(data).digest()).rstrip(b"=").decode()
        records.append(f"{arcname},sha256={digest},{len(data)}")

    di = f"{name}-{version}.dist-info"
    with zipfile.ZipFile(wheel_path, "w") as zf:
        add(zf, f"{name}/__init__.py", body)
        add(zf, f"{di}/METADATA",
            f"Metadata-Version: 2.1\nName: {name}\n"
            f"Version: {version}\n".encode())
        add(zf, f"{di}/WHEEL",
            b"Wheel-Version: 1.0\nGenerator: test\n"
            b"Root-Is-Purelib: true\nTag: py3-none-any\n")
        records.append(f"{di}/RECORD,,")
        zf.writestr(f"{di}/RECORD", "\n".join(records) + "\n")
    return wheel_path


def test_env_vars_reach_task(ray_session):
    @ray_tpu.remote(runtime_env={"env_vars": {"RTENV_PROBE": "ping"}})
    def probe():
        return os.environ.get("RTENV_PROBE")

    assert ray_tpu.get(probe.remote(), timeout=120) == "ping"


def test_working_dir_import_and_cwd(ray_session, tmp_path):
    wd = tmp_path / "app"
    wd.mkdir()
    (wd / "localmod.py").write_text("ANSWER = 41\n")
    (wd / "data.txt").write_text("payload")

    @ray_tpu.remote(runtime_env={"working_dir": str(wd)})
    def use_it():
        import localmod                      # only on the worker's path
        with open("data.txt") as f:          # cwd is the working_dir
            return localmod.ANSWER + 1, f.read()

    val, data = ray_tpu.get(use_it.remote(), timeout=120)
    assert val == 42 and data == "payload"
    with pytest.raises(ImportError):
        import localmod  # noqa: F401  (driver must NOT see it)


def test_py_modules(ray_session, tmp_path):
    pkg = tmp_path / "extpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("WHO = 'py_modules'\n")

    @ray_tpu.remote(runtime_env={"py_modules": [str(pkg)]})
    def who():
        import extpkg
        return extpkg.WHO

    assert ray_tpu.get(who.remote(), timeout=120) == "py_modules"


@pytest.mark.slow
def test_pip_wheel_in_actor(ray_session, tmp_path):
    """An actor imports a pip package the driver doesn't have (venv created
    with --system-site-packages, wheel installed offline)."""
    wheel = _make_wheel(tmp_path)

    @ray_tpu.remote(runtime_env={"pip": [wheel]})
    class UsesPkg:
        def magic(self):
            import rttestpkg
            return rttestpkg.MAGIC

        def has_numpy(self):
            import numpy                     # system site-packages intact
            return numpy.__name__

    a = UsesPkg.remote()
    assert ray_tpu.get(a.magic.remote(), timeout=300) == 12345
    assert ray_tpu.get(a.has_numpy.remote(), timeout=120) == "numpy"
    ray_tpu.kill(a)
    with pytest.raises(ImportError):
        import rttestpkg  # noqa: F401

    # cache hit: the same env resolves to the same venv without a rebuild
    from ray_tpu._private.runtime_env import get_manager
    mgr = get_manager()
    exe1, site1 = mgr._setup_pip([wheel])
    exe2, _ = mgr._setup_pip([wheel])
    assert exe1 == exe2 and os.path.exists(exe1)
    assert site1 and os.path.isdir(site1)
    # a REBUILT wheel at the same path must get a fresh venv
    os.utime(wheel, (os.path.getmtime(wheel) + 5,) * 2)
    exe3, _ = mgr._setup_pip([wheel])
    assert exe3 != exe1


def test_bad_pip_env_fails_cleanly(ray_session):
    @ray_tpu.remote(
        runtime_env={"pip": ["definitely-not-a-package-xyz-000"]})
    def f():
        return 1

    with pytest.raises(RuntimeEnvSetupError):
        ray_tpu.get(f.remote(), timeout=300)


def test_working_dir_on_remote_node(ray_session, tmp_path):
    """A daemon materializes the env for its own workers."""
    from ray_tpu.cluster_utils import Cluster
    wd = tmp_path / "napp"
    wd.mkdir()
    (wd / "nodemod.py").write_text("V = 'remote-env'\n")
    c = Cluster.attach()
    nid = c.add_node({"CPU": 2, "envres": 1})
    try:
        @ray_tpu.remote(resources={"envres": 1},
                        runtime_env={"working_dir": str(wd)})
        def use_it():
            import nodemod
            return os.environ.get("RAY_TPU_NODE_ID"), nodemod.V

        host, v = ray_tpu.get(use_it.remote(), timeout=180)
        assert host == nid and v == "remote-env"
    finally:
        c.kill_node(nid)
