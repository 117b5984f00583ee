"""chip_smoke.py's phases at tiny size on the CPU.

The script itself only runs on a TPU; here each phase function runs on the
session's CPU backend, with the Pallas interpreter chosen by the test (the
script never interprets). main() must refuse a non-TPU platform, and an
absorbed dispatch fault must fail the served-path and mesh phases.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def test_kernels_phase_binds_identically():
    info = chip_smoke.phase_kernels(num_pods=200, num_nodes=60,
                                    interpret=True, iters=1)
    assert info["identical"] == "pallas=xla=floor"
    bound, total = map(int, info["bound"].split("/"))
    assert 0 < bound <= total == 200


def test_served_phase_matches_serial_twin():
    info = chip_smoke.phase_served(num_pods=300, num_nodes=80, cycles=2,
                                   arrivals=40)
    assert info["twin"] == "identical" and info["ladder"] == "full"
    assert info["retries"] == 0 and all(n > 0 for n in info["bound"])
    # cycle 0 holds 300 pending pods: auto-K fuses it
    assert info["kernels"][0].startswith("chain(")


def _fault_once():
    fired = []

    def inject(stage):
        if not fired:
            fired.append(stage)
            raise RuntimeError("injected dispatch fault")

    return fired, inject


def test_served_phase_fails_on_absorbed_fault():
    fired, inject = _fault_once()
    with pytest.raises(chip_smoke.SmokeFailure, match="dispatch retries"):
        chip_smoke.phase_served(num_pods=120, num_nodes=40, cycles=0,
                                fault_injector=inject)
    assert fired


def test_sync_phase_reports_a_median():
    info = chip_smoke.phase_sync(iters=3)
    assert info["noop_jit_readback_median_ms"] >= 0


def test_mesh_phase_on_four_virtual_devices(cpu_devices):
    if len(cpu_devices) < 4:
        pytest.skip("needs 4 virtual CPU devices")
    info = chip_smoke.phase_mesh(ndev=4, num_nodes=200, pending=150,
                                 total_pods=600)
    assert info["mesh_devices"] == 4 and info["bindings"] == "mesh=single"
    assert info["retries"] == 0
    assert all(n > 0 for n in info["shard_rows"])


def test_mesh_phase_fails_on_absorbed_fault(cpu_devices):
    if len(cpu_devices) < 4:
        pytest.skip("needs 4 virtual CPU devices")
    fired, inject = _fault_once()
    with pytest.raises(chip_smoke.SmokeFailure, match="dispatch retries"):
        chip_smoke.phase_mesh(ndev=4, num_nodes=200, pending=150,
                              total_pods=600, cycles=0,
                              fault_injector=inject)
    assert fired


def test_main_refuses_a_non_tpu_platform(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "_ensure_floor", lambda: None)
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_main_fails_outside_a_checkout(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "HERE", str(tmp_path))
    assert chip_smoke.main([]) == 2
    assert capsys.readouterr().out == ""
