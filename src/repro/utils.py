"""Small shared utilities: tree helpers, formatting, deterministic hashing."""
from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np


#: the checkout root (``src/repro/utils.py`` -> ``.``): a fixed path, so the
#: compile cache keyed under it is found again by the next run
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Entry points call this once, before their first compile.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and nothing is
    set here; otherwise the persistent cache goes to ``<checkout>/.jax_cache``.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def pin_host_cpu() -> None:
    """Keep this process's JAX on the CPU platform.  AVEC's host is the weak
    device: a host process (or a parent that spawns a destination) must
    never take the accelerator its destination needs.  Call before the
    first JAX computation; raises if JAX already initialised another
    platform, since the pin could no longer take effect."""
    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"JAX already runs on {jax.default_backend()!r} in this process; "
            "pin_host_cpu() must run before the first JAX call")


def tree_bytes(tree: Any) -> int:
    """Total bytes of all array leaves (works on concrete and abstract leaves)."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape, dtype=np.int64)) * np.dtype(leaf.dtype).itemsize
    return total


def tree_params(tree: Any) -> int:
    """Total element count of all array leaves."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(l.shape, dtype=np.int64)) for l in leaves if hasattr(l, "shape"))


def fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} PiB"


def fmt_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


def stable_hash(obj: Any) -> str:
    """Deterministic content hash of a JSON-able object (or bytes)."""
    if isinstance(obj, bytes):
        payload = obj
    else:
        payload = json.dumps(obj, sort_keys=True, default=str).encode()
    return hashlib.sha256(payload).hexdigest()[:16]


def check_finite(tree: Any, name: str = "tree") -> None:
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if not bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32)))):
            raise FloatingPointError(f"non-finite values in {name}{jax.tree_util.keystr(path)}")


class Stopwatch:
    """Wall-clock stopwatch with named laps (used by the AVEC profiler)."""

    def __init__(self) -> None:
        self.laps: dict[str, float] = {}
        self._t0 = time.perf_counter()

    def lap(self, name: str) -> float:
        now = time.perf_counter()
        dt = now - self._t0
        self.laps[name] = self.laps.get(name, 0.0) + dt
        self._t0 = now
        return dt

    def total(self) -> float:
        return sum(self.laps.values())


def round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


def chunks(seq: Iterable, size: int):
    buf = []
    for item in seq:
        buf.append(item)
        if len(buf) == size:
            yield buf
            buf = []
    if buf:
        yield buf
