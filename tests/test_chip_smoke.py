"""chip_smoke.py: it refuses to run without a TPU, and its phases pass on
the CPU at small sizes (the same checks the chip run makes)."""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import get_arch, reduced
from repro.core.library import make_model_library, make_openpose_library
from repro.launch.serve import start_destination
from repro.models.openpose import OpenPoseLite
from repro import avec

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, timeout=300, env=env, cwd=ROOT)
    assert out.returncode != 0, out.stdout[-2000:]
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr, out.stderr[-2000:]


def test_chip_smoke_phases_on_cpu(smoke):
    cpu = jax.devices("cpu")[0]
    cfg = reduced(get_arch("granite-3-2b"))
    dest = start_destination(
        {"openpose": make_openpose_library(OpenPoseLite()),
         "lm": make_model_library(cfg, max_cache_len=32)},
        name="smoke-cpu", device=cpu)
    try:
        with avec.connect([dest.address], shadow_every=0) as client:
            assert client.capabilities(client.destinations[0]).raw[
                "device"]["platform"] == "cpu"
            smoke.openpose_phase(client, dest, cpu, 0, frames=4,
                                 hw=(64, 96))
            smoke.granite_phase(client, dest, cpu, 0, cfg=cfg, prompt=8,
                                steps=4)
    finally:
        dest.stop()


FOUR = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import jax
import chip_smoke
devs = jax.devices()
assert len(devs) == 4, devs
chip_smoke.four_destination_phase(devs, devs[0], 0, hw=(32, 48),
                                  map_frames=8, batch=16,
                                  shard_min_rows=4, check_peak=False)
print("FOUR_OK")
"""


def test_four_destination_phase_on_virtual_cpus():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", FOUR, ROOT],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_OK" in out.stdout
    lines = [l for l in out.stdout.splitlines() if l.startswith("[four]")]
    # one executor per device, each holding its own params
    homes = [l for l in lines if "params on" in l]
    assert len(homes) == 4 and len({l.split("params on ")[1]
                                    for l in homes}) == 4, lines
