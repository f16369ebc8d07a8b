"""Arithmetic the per-layer metric readers share."""

from __future__ import annotations


def mfu(r) -> float | None:
    """Model FLOPs of the window's work over (window x chips x peak), %."""
    run = r.run
    window = r.result["stats"].get("window_s")
    if not run.flops_in_window or not window or r.peaks is None:
        return None
    peak = r.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * run.flops_in_window / (window * peak)


def idle_share(r) -> float | None:
    """Share of the traced window in which no operation ran on a device."""
    if r.reduced is None or r.reduced["window_s"] <= 0 or not r.reduced[
            "devices"]:
        return None
    return 100.0 * (1.0 - r.reduced["busy_s"] / r.reduced["window_s"])


def stat(r, key: str) -> float | None:
    return r.result["stats"].get(key)
