"""Operations and bytes one served call needs, from the configuration alone.
Each architecture counts them in ``bench/arch/<reference>.py``; this file
holds what they share and the table of peaks.

This counts the work the algorithm requires, never what an implementation
happens to do, so a kernel that does less raises its share and no count can
push a share past 100 %:

* causal attention counts only the keys at or before each query;
* decode reads every weight once and the cached keys and values only up to
  the current position;
* bytes are counted at the configuration's compute dtype (bfloat16 for
  every configuration so far), whatever dtype the program keeps its cache
  in;
* the SSD state is read and written once per step, and its recurrence is
  counted as the linear-time scan (3 operations per state element to decay
  and update it, 2 to read it out);
* prefill needs logits at its last position only (that is all it returns).

Peaks come from ``bench/peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def itemsize(c: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[c["torch_dtype"]]


def cost(c: dict, fn: str, n: int) -> tuple[float, float]:
    """(operations, bytes) one call needs, by the ``cost`` of
    ``bench/arch/<reference>.py``.  ``n``: the prompt length for prefill;
    for decode the position of the token fed (it attends to n + 1
    positions)."""
    from bench import harness
    return harness.arch_module(c["reference"]).cost(c, fn, n)


def parts(c: dict, fn: str, n: int) -> dict:
    """``{scope: (operations, bytes)}`` that each named program scope of
    one call needs, by the ``parts`` of ``bench/arch/<reference>.py``."""
    from bench import harness
    return harness.arch_module(c["reference"]).parts(c, fn, n)


def least_s(ops: float, nbytes: float, peak: dict) -> float:
    """Roofline time: the larger of operations over peak rate and bytes
    over peak bandwidth."""
    return max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def min_time_s(c: dict, fn: str, n: int, peak: dict) -> float:
    """Roofline time of one call."""
    return least_s(*cost(c, fn, n), peak)
