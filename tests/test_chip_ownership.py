"""Chip ownership: the driver stays off the device, each launched worker
owns one TPU chip, and a chip worker never falls back to the CPU.

No test here loads the TPU library: chip assignment is checked through a
stand-in worker executable that records its environment, and the
no-fallback rule through a chip worker whose platform is the CPU.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

import repro.core as rc
from repro.cache import DEFAULT_DIR
from repro.core.backends import launchers
from repro.core.backends.launchers import CHIP_ENV, LocalLauncher

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


@pytest.fixture(autouse=True)
def _cleanup():
    yield
    rc.shutdown()
    rc.plan("sequential")


def _run(code: str, env_extra=None, drop=(), timeout=240):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_driver_never_initialises_a_jax_backend():
    out = _run("""
        import json
        import jax, jax.numpy as jnp
        from jax._src import xla_bridge
        import repro.core as rc
        from repro.launch.train import MultiPodDriver, PodRunConfig

        rc.set_session_seed(7)
        rc.plan("cluster", workers=1)
        key = rc.value(rc.future(
            lambda key: jax.random.normal(key, (3,)), seed=True))
        arr = rc.value(rc.future(lambda: jnp.arange(4.0) * 2))
        hist = MultiPodDriver(PodRunConfig(
            pods=1, rounds=1, local_steps=1, batch=2, seq=16,
            smoke=True)).run()
        print(json.dumps({
            "initialized": xla_bridge.backends_are_initialized(),
            "key": key.tolist(), "arr_type": type(arr).__name__,
            "arr": arr.tolist(), "loss": hist[0]["loss"]}))
        rc.shutdown()
    """)
    assert out["initialized"] is False
    # the key the worker derived from (seed, index) is the key any backend
    # derives: first seeded future of session 7
    want = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(7), 0), (3,))
    np.testing.assert_array_equal(np.asarray(out["key"], np.float32),
                                  np.asarray(want))
    assert out["arr_type"] == "ndarray" and out["arr"] == [0, 2, 4, 6]
    assert np.isfinite(out["loss"])


@pytest.mark.parametrize("preset", [None, "/explicit/cache/dir"])
def test_compile_cache_placement(preset):
    code = """
        import json, os
        from repro.cache import enable_compile_cache
        import jax
        path = enable_compile_cache()
        print(json.dumps({"path": path,
                          "jax": jax.config.jax_compilation_cache_dir,
                          "env": os.environ["JAX_COMPILATION_CACHE_DIR"]}))
    """
    if preset is None:
        out = _run(code, drop=("JAX_COMPILATION_CACHE_DIR",))
        want = DEFAULT_DIR
        assert want == os.path.join(os.path.dirname(SRC), ".jax_cache")
    else:
        out = _run(code, env_extra={"JAX_COMPILATION_CACHE_DIR": preset})
        want = preset
    # launched workers inherit the directory through the environment
    assert out == {"path": want, "jax": want, "env": want}


def _tpu_host(monkeypatch, on_pci: int, device_files: int) -> None:
    """Pretend this host has ``on_pci`` TPU chips on its PCI bus, of which
    ``device_files`` have a device file here."""
    from jax._src import hardware_utils
    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (on_pci, None))
    monkeypatch.setattr(launchers, "_tpu_device_files",
                        lambda: device_files)
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)


@pytest.mark.parametrize("env,on_pci,files,want", [
    ({"JAX_PLATFORMS": "cpu"}, 4, 4, []),
    ({"JAX_PLATFORMS": "tpu,cpu"}, 4, 4, ["0", "1", "2", "3"]),
    ({}, 2, 2, ["0", "1"]),
    # a machine given one chip of a four-chip host: all four on the PCI
    # bus, one device file
    ({"JAX_PLATFORMS": "tpu,cpu"}, 4, 1, ["0"]),
    ({"JAX_PLATFORMS": "tpu"}, 0, 0, []),
    # the chips this process may use were handed down to it
    ({"JAX_PLATFORMS": "tpu", "TPU_VISIBLE_CHIPS": "2,3"}, 4, 4,
     ["2", "3"]),
], ids=["cpu", "whole-host", "platform-unset", "partitioned-host",
        "no-chips", "inherited-visible-chips"])
def test_host_tpu_chips(monkeypatch, env, on_pci, files, want):
    _tpu_host(monkeypatch, on_pci, files)
    assert launchers.host_tpu_chips(env) == want


@pytest.fixture
def fake_worker(tmp_path, monkeypatch):
    """A stand-in worker executable on a host with two TPU chips: records
    its environment, then idles until killed (it never dials the
    driver)."""
    _tpu_host(monkeypatch, 2, 2)
    exe = tmp_path / "fake-python"
    exe.write_text(f"#!/bin/sh\nenv > {tmp_path}/env.$$\nexec sleep 60\n")
    exe.chmod(0o755)
    return tmp_path


def _env_of(wp, tmp_path) -> dict:
    path = tmp_path / f"env.{wp.pid}"
    deadline = time.monotonic() + 10
    while not path.exists() or not path.read_text().endswith("\n"):
        assert time.monotonic() < deadline, "fake worker never started"
        time.sleep(0.05)
    return dict(line.split("=", 1) for line in
                path.read_text().splitlines() if "=" in line)


def test_each_launched_worker_owns_one_chip(fake_worker):
    launcher = LocalLauncher(python=str(fake_worker / "fake-python"),
                             env=(("JAX_PLATFORMS", "tpu,cpu"),))
    addr = ("127.0.0.1", 9)
    procs = [launcher.launch("127.0.0.1", addr, tag=f"t{i}")
             for i in range(2)]
    try:
        envs = [_env_of(wp, fake_worker) for wp in procs]
        assert sorted(e[CHIP_ENV] for e in envs) == ["0", "1"]
        for e in envs:
            assert e["JAX_PLATFORMS"] == "tpu"          # no CPU fallback
            assert e["TPU_VISIBLE_CHIPS"] == e[CHIP_ENV]
            assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert envs[0]["TPU_PROCESS_PORT"] != envs[1]["TPU_PROCESS_PORT"]
        # every chip is owned by a live worker: a third one is refused
        with pytest.raises(RuntimeError, match="no free TPU chip"):
            launcher.launch("127.0.0.1", addr, tag="t2")
        # a relaunch after a death takes over the dead worker's chip
        dead_chip = envs[0][CHIP_ENV]
        procs[0].kill()
        procs[0].wait(5)
        procs.append(launcher.launch("127.0.0.1", addr, tag="t3"))
        assert _env_of(procs[-1], fake_worker)[CHIP_ENV] == dead_chip
    finally:
        for wp in procs:
            wp.kill()
            wp.wait(5)


def test_inherited_visible_chips_are_the_ones_handed_out(fake_worker,
                                                         monkeypatch):
    """A driver that may use chips 2 and 3 of a four-chip host hands out
    exactly those, numbered as the host numbers them."""
    _tpu_host(monkeypatch, 4, 4)
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    launcher = LocalLauncher(python=str(fake_worker / "fake-python"),
                             env=(("JAX_PLATFORMS", "tpu,cpu"),))
    addr = ("127.0.0.1", 9)
    procs = [launcher.launch("127.0.0.1", addr, tag=f"v{i}")
             for i in range(2)]
    try:
        envs = [_env_of(wp, fake_worker) for wp in procs]
        assert sorted(e[CHIP_ENV] for e in envs) == ["2", "3"]
        for e in envs:
            assert e["TPU_VISIBLE_CHIPS"] == e[CHIP_ENV]
        with pytest.raises(RuntimeError, match=r"no free TPU chip.*\(2,3\)"):
            launcher.launch("127.0.0.1", addr, tag="v2")
    finally:
        for wp in procs:
            wp.kill()
            wp.wait(5)


def test_default_worker_count_is_one_per_chip(fake_worker, monkeypatch):
    from repro.core import planning
    monkeypatch.setattr(planning, "available_cores", lambda: 8)
    launcher = LocalLauncher(python=str(fake_worker / "fake-python"),
                             env=(("JAX_PLATFORMS", "tpu,cpu"),))
    try:
        # the stand-in workers never say hello: startup times out waiting
        # for two of them, with no launch refused for want of a chip
        with pytest.raises(rc.ChannelError,
                           match=r"0/2 workers connected") as err:
            rc.plan("cluster", launcher=launcher, connect_timeout=2)
            rc.active_backend()
        assert "no free TPU chip" not in str(err.value)
    finally:
        pids = [int(p.name.split(".")[1])
                for p in fake_worker.glob("env.*")]
        for pid in pids:
            try:
                os.kill(pid, 9)
            except ProcessLookupError:
                pass
    assert len(pids) == 2


def test_more_chip_workers_than_chips_fails_loudly(fake_worker):
    launcher = LocalLauncher(python=str(fake_worker / "fake-python"),
                             env=(("JAX_PLATFORMS", "tpu,cpu"),))
    with pytest.raises(rc.ChannelError, match="no free TPU chip"):
        rc.plan("cluster", workers=3, launcher=launcher,
                connect_timeout=20)
        rc.active_backend()


def test_no_chips_handed_out_when_the_platform_excludes_tpu(fake_worker):
    launcher = LocalLauncher(python=str(fake_worker / "fake-python"),
                             env=(("JAX_PLATFORMS", "cpu"),))
    wp = launcher.launch("127.0.0.1", ("127.0.0.1", 9), tag="cpu")
    try:
        env = _env_of(wp, fake_worker)
        assert CHIP_ENV not in env and env["JAX_PLATFORMS"] == "cpu"
    finally:
        wp.kill()
        wp.wait(5)


def test_chip_worker_without_a_tpu_dies_before_hello():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env[CHIP_ENV] = "0"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.core.backends.cluster_worker",
         "127.0.0.1:9"], env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "must hold exactly one TPU chip" in proc.stderr


@pytest.mark.parametrize("four_chips", [False, True])
def test_chip_smoke_rehearsal(tmp_path, four_chips):
    """``chip_smoke.py --rehearse`` drives every phase on CPU workers at
    the reduced config and ends on the result line."""
    script = os.path.join(os.path.dirname(SRC), "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax-cache"))
    cmd = [sys.executable, script, "--rehearse"]
    if four_chips:
        cmd.append("--four-chips")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": 4 if four_chips else 1}}


def test_chip_smoke_without_a_tpu_fails_and_prints_no_result():
    # JAX_PLATFORMS=cpu takes the TPU away even on a host that has one
    script = os.path.join(os.path.dirname(SRC), "chip_smoke.py")
    proc = subprocess.run([sys.executable, script],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
