"""chip_smoke.py's run function at a tiny size on the forced-CPU
platform: the same result checks the chip run makes (with the platform
requirement passed as ``cpu``), and the refusal to run anywhere but on the
required platform."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def test_smoke_refuses_the_wrong_platform(monkeypatch):
    def must_not_build(*_a, **_k):
        raise AssertionError("the smoke built a cluster on the wrong platform")

    monkeypatch.setattr(chip_smoke, "build_cluster", must_not_build)
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="platform is 'cpu'.*required 'tpu'"):
        chip_smoke.run(16, 512, platform="tpu")


def test_smoke_serves_tiny_cluster_on_cpu(capsys, monkeypatch):
    # A 5 ms inline wait makes every solve answer progress bodies first, so
    # the client's resume-by-User-Task-ID path runs as it does at 1,000
    # brokers (where a solve outlasts the default 10 s many times over).
    monkeypatch.setitem(chip_smoke.SMOKE_CONFIG,
                        "webserver.request.maxBlockTimeMs", 5)
    result = chip_smoke.run(16, 512, platform="cpu")
    import jax
    assert result == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    out = capsys.readouterr().out
    for label in ("state", "load", "proposals_cold", "proposals_steady",
                  "rebalance_dryrun"):
        assert f"smoke reading: {label}" in out
    # The served solve ran on every forced device (mesh chosen at the
    # entry point), and the steady requests solved without compiling.
    assert f"solver_devices {len(jax.devices())}" in out
