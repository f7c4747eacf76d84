"""The per-architecture hooks of bench/arch: a reference kind joins the
benchmark as new files, and the cost moved there counts as it did."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import flops, harness

GRANITE = harness.load_config(harness.config_path("granite-3-2b"))
MAMBA2 = harness.load_config(harness.config_path("mamba2-130m"))

# bench/flops.py's cost before it moved to bench/arch, at the prompt lengths
# the cells serve and at decode positions across the cache
PINNED = {
    ("granite-3-2b", "prefill", 640): (3181266350080.0, 5120023052.0),
    ("granite-3-2b", "prefill", 1280): (6496549089280.0, 5172454412.0),
    ("granite-3-2b", "prefill", 1920): (9946049556480.0, 5224885772.0),
    ("granite-3-2b", "prefill", 2560): (13529767751680.0, 5277317132.0),
    ("granite-3-2b", "prefill", 3200): (17247703674880.0, 5329748492.0),
    ("granite-3-2b", "decode", 640): (5276774400.0, 5120102416.0),
    ("granite-3-2b", "decode", 1400): (5525811200.0, 5182361616.0),
    ("granite-3-2b", "decode", 1930): (5699481600.0, 5225779216.0),
    ("granite-3-2b", "decode", 3199): (6115307520.0, 5329735696.0),
    ("granite-3-2b", "decode", 4095): (6408908800.0, 5403136016.0),
    ("mamba2-130m", "prefill", 640): (130719309312.0, 267976852.0),
    ("mamba2-130m", "prefill", 1280): (261361393152.0, 267979412.0),
    ("mamba2-130m", "prefill", 1920): (392003476992.0, 267981972.0),
    ("mamba2-130m", "prefill", 2560): (522645560832.0, 267984532.0),
    ("mamba2-130m", "prefill", 3200): (653287644672.0, 267987092.0),
    ("mamba2-130m", "decode", 640): (281353728.0, 277669528.0),
    ("mamba2-130m", "decode", 1400): (281353728.0, 277669528.0),
    ("mamba2-130m", "decode", 1930): (281353728.0, 277669528.0),
    ("mamba2-130m", "decode", 3199): (281353728.0, 277669528.0),
    ("mamba2-130m", "decode", 4095): (281353728.0, 277669528.0),
}

# granite-3-2b's attention core, all 40 layers: 4 * 32 heads * 64 operations
# per (query, key) pair; 2 * 8 KV heads * 64 * 2 bytes per layer and token
G_SDPA_PER_KEY = 40 * 4 * 32 * 64
G_KV_BYTES_PER_TOKEN = 40 * 2 * 8 * 64 * 2


@pytest.mark.parametrize("config,fn,n", sorted(PINNED))
def test_moved_cost_counts_as_before(config, fn, n):
    c = harness.load_config(harness.config_path(config))
    assert flops.cost(c, fn, n) == PINNED[(config, fn, n)]


def test_a_new_kind_needs_only_its_own_file(monkeypatch):
    stub = NS(program_config=lambda c: ("toy config", c["width"]),
              cost=lambda c, fn, n: (3.0 * n, 5.0 * n),
              parts=lambda c, fn, n: {"avec.toy": (1.0, 2.0)})
    kinds = []

    def lookup(kind):
        kinds.append(kind)
        return stub

    monkeypatch.setattr(harness, "arch_module", lookup)
    c = {"reference": "toy", "width": 7}
    assert harness.program_config(c) == ("toy config", 7)
    assert flops.cost(c, "decode", 4) == (12.0, 20.0)
    assert flops.parts(c, "decode", 4) == {"avec.toy": (1.0, 2.0)}
    assert kinds == ["toy"] * 3


def test_the_shared_files_name_no_kind():
    for f in ("harness.py", "flops.py"):
        with open(os.path.join(harness.BENCH, f)) as fh:
            text = fh.read()
        assert "dense_gqa" not in text and "mamba2" not in text, f


def test_a_kind_without_hooks_names_the_missing_file():
    with pytest.raises(FileNotFoundError,
                       match=os.path.join("bench", "arch", "nosuchkind.py")):
        harness.program_config({"reference": "nosuchkind"})


def test_sdpa_parts_by_hand():
    n = 1400        # decode: the token at 1400 attends to 1401 positions
    assert flops.parts(GRANITE, "decode", n) == {
        "avec.sdpa": (G_SDPA_PER_KEY * 1401, G_KV_BYTES_PER_TOKEN * 1401)}
    assert flops.parts(GRANITE, "decode", n)["avec.sdpa"] == (
        459_079_680.0, 114_769_920.0)
    n = 1280        # prefill: causal pairs only, and the K/V it writes
    assert flops.parts(GRANITE, "prefill", n) == {
        "avec.sdpa": (G_SDPA_PER_KEY * 1280 * 1281 / 2,
                      G_KV_BYTES_PER_TOKEN * 1280)}
    assert flops.parts(GRANITE, "prefill", n)["avec.sdpa"] == (
        268_645_171_200.0, 104_857_600.0)
    # the scope's needs are part of the whole call's
    for fn, n in (("decode", 1400), ("prefill", 1280)):
        ops, nbytes = flops.parts(GRANITE, fn, n)["avec.sdpa"]
        whole = flops.cost(GRANITE, fn, n)
        assert ops < whole[0] and nbytes < whole[1]


def test_a_kind_without_scopes_has_no_parts():
    assert flops.parts(MAMBA2, "decode", 300) == {}
    assert flops.parts(MAMBA2, "prefill", 512) == {}
