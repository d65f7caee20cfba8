"""Operation counts of the fit path and the table of device peaks.

One sweep over the kernel matrix evaluates every entry of K (distance and
profile, about 3d + 8 operations) and multiplies it into the 1 + s
right-hand sides (2 (1 + s) operations). An outer step makes one sweep per
solver epoch, one for the initial residual and one for the gradient pass.
"""
from __future__ import annotations

from pathlib import Path

from bench.spec import load_json

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def sweep_flops(n: int, d: int, num_probes: int) -> float:
    """Operations of one sweep over the n x n kernel matrix."""
    return float(n) * n * (3 * d + 8 + 2 * (1 + num_probes))


def step_flops(n: int, d: int, num_probes: int, epochs: float) -> float:
    """Operations of one outer step that ran ``epochs`` solver epochs."""
    return sweep_flops(n, d, num_probes) * (epochs + 2)


def peak(device_kind: str, what: str = "bf16_flops_per_s") -> float:
    """The published peak of one chip of ``device_kind``; a kind that is
    not in the table is an error."""
    table = load_json(PEAKS)["peaks"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS}; "
                       f"known: {sorted(table)}")
    return float(table[device_kind][what])
