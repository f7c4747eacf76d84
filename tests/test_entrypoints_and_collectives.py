"""Launch entrypoints + hierarchical compressed collectives."""
import json
import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_train_entrypoint_cli():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "granite-3-2b",
         "--steps", "5", "--seq-len", "16", "--batch", "4"],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "loss" in out.stdout


def test_serve_entrypoint_cli():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--role", "local",
         "--reduced", "--requests", "2", "--max-len", "48"],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-1500:]
    # entrypoints log structured JSON (repro.obs.trace.emit), one per line
    events = [json.loads(line) for line in out.stdout.splitlines()
              if line.startswith("{")]
    done = [e for e in events if e["event"] == "engine_complete"]
    assert done and done[0]["tokens"] > 0 and done[0]["tok_per_s"] > 0


CACHE_PROBE = r"""
import sys
sys.path.insert(0, sys.argv[1])
import jax
from repro.utils import enable_compile_cache, pin_host_cpu
pin_host_cpu()
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
print(jax.default_backend())
"""


@pytest.mark.parametrize("env_dir", [None, "/some/cache/dir"])
def test_compile_cache_location(env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set in code;
    without it the cache sits at the fixed <checkout>/.jax_cache."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", CACHE_PROBE, SRC],
                         capture_output=True, text=True, timeout=120,
                         env=env)
    assert out.returncode == 0, out.stderr[-1500:]
    used, configured, backend = out.stdout.split()
    want = env_dir or os.path.join(os.path.abspath(
        os.path.join(SRC, "..")), ".jax_cache")
    assert used == configured == want
    assert backend == "cpu"


def test_dcn_wire_accounting():
    from repro.distributed.collectives import dcn_wire_bytes
    tree = {"w": jnp.zeros((64, 128))}
    raw = dcn_wire_bytes(tree, compressed=False)
    comp = dcn_wire_bytes(tree, compressed=True)
    assert raw == 64 * 128 * 4
    assert comp == 64 * 128 + 64 * 4
    assert comp < raw / 3


def test_compressed_psum_single_axis():
    """compressed_psum == psum(quant-dequant) numerics on a 1-device mesh."""
    from jax.sharding import AxisType
    from repro.optim.compression import compressed_psum
    mesh = jax.make_mesh((1,), ("pod",), axis_types=(AxisType.Auto,))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))

    def f(t):
        return compressed_psum({"g": t}, "pod")["g"]

    out = jax.experimental.shard_map.shard_map(
        f, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec(), check_rep=False)(x)
    bound = np.abs(np.asarray(x)).max(axis=-1, keepdims=True) / 127.0
    assert np.all(np.abs(np.asarray(out) - np.asarray(x)) <= bound + 1e-6)
