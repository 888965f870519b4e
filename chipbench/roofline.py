"""Peaks and the least time of a solve, counted from the matrix alone.

A sparse triangular solve is bound by memory bandwidth: it does two flops per
nonzero and reads each nonzero once. Its essential bytes are those of the CSR
form of L, whatever a program stores instead: a 4-byte value and a 4-byte
column index per nonzero and a 4-byte row pointer per row, plus ``R * n``
4-byte words of ``b`` read and of ``x`` written. The least time is those bytes
over the HBM bandwidth of every chip used. A store that moves more than that
(dense tiles, replicated blocks) shows as a low share, which is what it is.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


def essential_bytes(n: int, nnz: int, R: int = 1) -> int:
    return 8 * nnz + 4 * n + 2 * 4 * R * n


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def least_time_s(n: int, nnz: int, R: int, chips: int, device_kind: str) -> float:
    return essential_bytes(n, nnz, R) / (chips * peaks(device_kind)["hbm_bytes_per_s"])


def idle_share(run: dict) -> float | None:
    """Percent of the traced window in which no op ran (mean over chips)."""
    t = run["trace"]
    if not t or not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline_share(run: dict) -> float | None:
    """Percent: the least time of one solve over the device's busy time per
    solve in the traced window."""
    t = run["trace"]
    if not t or not t["devices"] or t["busy_s"] <= 0 or not run["solves"]:
        return None
    least = least_time_s(run["n"], run["nnz"], run["rhs_columns"], run["chips"],
                         run["device_kind"])
    return 100.0 * least / (t["busy_s"] / run["solves"])
