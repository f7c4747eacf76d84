"""Serving engine equivalence + sharding-rule unit tests + a subprocess
mini dry-run (8 fake devices) proving the launch path end-to-end."""
import json
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import model as M
from repro.models.params import ParamSpec
from repro.serving.engine import Request, ServingEngine, generate_sequential

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["granite-3-2b", "mamba2-130m",
                                  "jamba-1.5-large-398b"])
def test_continuous_batching_matches_sequential(arch):
    cfg = reduced(get_arch(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_batch=3, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [Request(f"r{i}", rng.integers(0, cfg.vocab_size,
                                          size=rng.integers(3, 10)).tolist(),
                    max_new_tokens=6) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    out = eng.run()
    for r in reqs:
        want = generate_sequential(cfg, params, r.prompt, 6, max_len=64)
        assert out[r.rid] == want, (arch, r.rid)


def test_engine_respects_eos():
    cfg = reduced(get_arch("granite-3-2b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    probe = ServingEngine(cfg, params, max_batch=1, max_len=32)
    probe.submit(Request("p", [1, 2, 3], max_new_tokens=8))
    full = probe.run()["p"]
    eos = full[2]
    eng = ServingEngine(cfg, params, max_batch=1, max_len=32)
    eng.submit(Request("q", [1, 2, 3], max_new_tokens=8, eos_id=eos))
    got = eng.run()["q"]
    # stops at the FIRST eos occurrence (numerics may repeat tokens earlier)
    assert got == full[:full.index(eos) + 1]


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_sharding_rules_divisibility_and_profiles():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import PROFILES, spec_to_pspec
    from repro.launch.mesh import make_host_mesh

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    mesh = FakeMesh()
    # vocab padded to 2048-multiple always divides
    s = ParamSpec((51200, 2048), ("vocab", "embed"))
    assert spec_to_pspec(mesh, s, "dp_tp") == P("model", None)
    assert spec_to_pspec(mesh, s, "fsdp_tp") == P("model", "data")
    # uneven heads replicate (36 % 16 != 0)
    s = ParamSpec((2304, 36, 64), ("embed", "heads", "head_dim"))
    assert spec_to_pspec(mesh, s, "dp_tp") == P(None, None, None)
    # even heads shard
    s = ParamSpec((4096, 32, 128), ("embed", "heads", "head_dim"))
    assert spec_to_pspec(mesh, s, "dp_tp") == P(None, "model", None)
    # experts shard over model
    s = ParamSpec((128, 7168, 4864), ("experts", "embed", "expert_mlp"))
    assert spec_to_pspec(mesh, s, "dp_tp") == P("model", None, None)
    # fsdp never double-books a mesh axis
    s = ParamSpec((2048, 2048), ("embed", "embed"))
    p = spec_to_pspec(mesh, s, "fsdp_tp")
    assert p == P("data", None)


def test_every_arch_param_axes_cover_shapes():
    """Every ParamSpec's axes tuple matches its shape rank (catches spec
    drift when editing models)."""
    from repro.configs import ARCH_IDS
    from repro.models.params import is_spec

    for arch in ARCH_IDS:
        cfg = get_arch(arch)
        specs = M.param_specs(cfg)
        for path, spec in jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=is_spec):
            assert len(spec.shape) == len(spec.axes), \
                (arch, jax.tree_util.keystr(path))


# ---------------------------------------------------------------------------
# launch path: subprocess mini dry-run on 8 fake devices
# ---------------------------------------------------------------------------

MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, dataclasses
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs import get_arch, reduced, SHAPES
from repro.distributed import sharding as sh
from repro.launch.dryrun import build_cell
from repro.launch.roofline import parse_collective_bytes

cfg = dataclasses.replace(reduced(get_arch(sys.argv[2])),
                          num_heads=4, num_kv_heads=4, unroll_blocks=True)
shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
fn, args, in_sh, out_sh = build_cell(cfg, shape, mesh, "dp_tp")
with mesh:
    compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args).compile()
ca = compiled.cost_analysis()
coll, by_type = parse_collective_bytes(compiled.as_text())
print(json.dumps({"flops": float(ca.get("flops", 0)), "coll": coll,
                  "ops": sorted(by_type)}))
"""


@pytest.mark.parametrize("arch", ["granite-3-2b", "moonshot-v1-16b-a3b"])
def test_mini_dryrun_subprocess(arch):
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(MINI_DRYRUN)
        path = f.name
    try:
        out = subprocess.run([sys.executable, path, SRC, arch],
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        assert rec["flops"] > 0
        # data-parallel training must reduce gradients -> all-reduce present
        assert "all-reduce" in rec["ops"], rec
    finally:
        os.unlink(path)
