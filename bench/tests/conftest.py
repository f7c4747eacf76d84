import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (os.path.join(_ROOT, "src"), _ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def tiny_configs() -> list[str]:
    """The tiny configurations: the files of ``data/`` that name a
    reference kind (a traffic file names none)."""
    names = []
    for f in sorted(os.listdir(DATA)):
        if f.endswith(".json"):
            with open(os.path.join(DATA, f)) as fh:
                if "reference" in json.load(fh):
                    names.append(f[:-len(".json")])
    return names


@pytest.hookimpl(trylast=True)
def pytest_generate_tests(metafunc):
    """A test that takes ``tiny_config`` runs once per tiny configuration,
    so a new architecture's file in ``data/`` joins every such test (its
    id comes after those of the test's own parameters)."""
    if "tiny_config" in metafunc.fixturenames:
        metafunc.parametrize("tiny_config", tiny_configs())
