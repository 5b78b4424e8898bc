"""Least work of a scoring round, from its shapes alone, and the chip's
published peaks.

The bytes are what any implementation of one round's TOPSIS scoring has
to move through HBM: the per-kind criteria tensor read once, the
validity mask and the weights read, the closeness written. They do not
depend on how the program pads, gathers or fuses, so a later change of
the scoring path is judged on the same work.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def score_round_bytes(p: int, k: int, n: int, c: int) -> int:
    """``K*N*C*4`` (kind tensor, float32) + ``P*N`` (bool mask) +
    ``P*C*4`` (weights) + ``P*N*4`` (closeness written), with P the
    round's real queue length and K the kinds held."""
    return k * n * c * 4 + p * n + p * c * 4 + p * n * 4


def peak(device_kind: str, key: str) -> float:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS.name}; add them with their source")
    return float(table[device_kind][key])
