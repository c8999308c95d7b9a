"""chip_smoke.py's checks at a tiny size on the CPU (kernels in interpret mode).

The script runs these same functions at the 2-NN's published width on the
chip; here each phase pins that the kernel path and the XLA path agree
within the script's bound and that the loss falls.
"""
import os
import subprocess
import sys
import textwrap

import pytest

import chip_smoke

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.mark.parametrize("phase", sorted(chip_smoke.PHASES))
def test_phase_kernel_matches_xla(phase):
    res = chip_smoke.run_phase(phase, n=16, d_in=64, batch=8, batch_pool=4,
                               block_size=4, events=4)
    assert chip_smoke.phase_failures(res, need_custom_call=False) == []
    assert res["kernel"]["events"] == res["xla"]["events"] == 4


def test_phase_failures_reports_each_broken_check():
    ok = {"events": 4, "loss0": 2.0, "loss": 1.0, "tpu_custom_call": False}
    res = {"events": 4, "kernel": dict(ok, loss=float("nan")),
           "xla": dict(ok, events=3),
           "diff": {"W": 2 * chip_smoke.BOUND, "y": 0.0, "loss": 0.0}}
    msgs = chip_smoke.phase_failures(res, need_custom_call=True)
    assert len(msgs) == 4, msgs


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    assert '"ok"' not in capsys.readouterr().out


def test_four_chip_checks_on_virtual_devices():
    """The --four-chips checks on four CPU devices, in a child process so
    this one keeps its single device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    code = textwrap.dedent("""
        import chip_smoke as cs
        ring = cs.ring_gossip_check(d_in=64)
        train = cs.mesh_train_check(steps=2)
        assert cs.four_chip_failures(ring, train) == [], (ring, train)
        print("FOUR_OK", ring["diff"], train["mesh"])
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "FOUR_OK" in out.stdout
