"""Arithmetic of the measured window."""

from __future__ import annotations


def rate(units: float, t_start: float, t_end: float) -> float:
    """Work over the whole window: every unit and every second of it."""
    if t_end <= t_start:
        raise ValueError(f"empty window: {t_start} .. {t_end}")
    return units / (t_end - t_start)
