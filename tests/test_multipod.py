"""Multi-pod driver: convergence, node failure, stragglers, elasticity.

These run the REAL driver — pods are worker processes attached to the TCP
socket cluster backend — on reduced configs: the CPU-scale simulation of
the 1000-node story, now over the same transport a real deployment uses.
"""

import pytest

import repro.core as rc
from repro.launch.train import MultiPodDriver, PodRunConfig


@pytest.fixture(autouse=True)
def _cleanup():
    yield
    rc.shutdown()
    rc.plan("sequential")


def _cfg(**kw):
    base = dict(arch="xlstm-125m", pods=2, rounds=3, local_steps=3,
                batch=2, seq=32, smoke=True)
    base.update(kw)
    return PodRunConfig(**base)


def test_multipod_loss_decreases():
    driver = MultiPodDriver(_cfg())
    hist = driver.run()
    assert len(hist) == 3
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_multipod_compression_matches_uncompressed_roughly():
    d1 = MultiPodDriver(_cfg(compress=True))
    h1 = d1.run()
    rc.shutdown()
    d2 = MultiPodDriver(_cfg(compress=False))
    h2 = d2.run()
    # int8+EF must not derail the loss trajectory
    assert abs(h1[-1]["loss"] - h2[-1]["loss"]) < 0.5


def test_multipod_survives_node_failure(tmp_path):
    marker = str(tmp_path / "pod-died")
    driver = MultiPodDriver(_cfg(fail_marker=marker, rounds=2))
    hist = driver.run()
    assert len(hist) == 2                  # round completed despite the kill
    import os
    assert os.path.exists(marker)          # the failure really happened


def test_multipod_straggler_speculation():
    import time
    driver = MultiPodDriver(_cfg(
        pods=2, rounds=1, straggle_pod=1, straggle_s=30.0,
        straggler_timeout_s=2.0))
    t0 = time.time()
    hist = driver.run()
    wall = time.time() - t0
    assert len(hist) == 1
    assert wall < 25.0                     # did not wait out the straggler


def test_multipod_elastic_resize():
    driver = MultiPodDriver(_cfg(rounds=1))
    driver.run_round(0)
    driver.resize(3)
    rec = driver.run_round(1)
    assert rec["round"] == 1
    assert driver.cfg.pods == 3


def test_multipod_checkpoints(tmp_path):
    driver = MultiPodDriver(_cfg(rounds=2, ckpt_dir=str(tmp_path / "ck")))
    driver.run()
    assert driver.ckpt.latest_step() == 2


def test_multipod_pod_that_always_dies_raises(monkeypatch):
    """A pod that dies on every try (device OOM, runtime abort) is
    re-dispatched at most MAX_REDISPATCH times, then the round raises."""
    import repro.launch.train as train
    from repro.core import future
    monkeypatch.setattr(train, "MAX_REDISPATCH", 1)

    def always_dies():
        import os
        os._exit(43)

    class Doomed(MultiPodDriver):
        def _dispatch(self, pod, rnd, *, speculative=False):
            return future(always_dies, label=f"pod{pod}-round{rnd}")

    driver = Doomed(_cfg(pods=1, rounds=1))
    with pytest.raises(RuntimeError, match="pod 0 failed 2 times"):
        driver.run_round(0)
