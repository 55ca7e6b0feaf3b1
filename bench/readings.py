"""Shared arithmetic of the per-layer metric readers (bench/metrics/).

A reader gets the run's record: ``window`` (what the driver counted on
the host clock), ``obs`` (the ``repro.obs`` registry snapshot of the
window), ``trace`` (``trace_reduce`` output, with ``window_s``),
``peaks`` (the chip's row of ``peaks.json``) and ``chips``. It returns
``None`` where there is nothing to read, and the harness then leaves the
metric out of the line.
"""
from __future__ import annotations


def _select(section: dict, name: str) -> list:
    return [v for k, v in section.items()
            if k == name or k.startswith(name + "{")]


def counter(rec: dict, name: str, **labels) -> float | None:
    """Sum of a ``repro.obs`` counter over its label sets (those that
    carry every given label value)."""
    snap = rec.get("obs")
    if not snap:
        return None
    vals = [v for k, v in snap["counters"].items()
            if (k == name or k.startswith(name + "{"))
            and all(f'{lk}="{lv}"' in k for lk, lv in labels.items())]
    return float(sum(vals)) if vals else None


def histogram(rec: dict, name: str) -> tuple[float, int] | None:
    """(sum, count) of a ``repro.obs`` histogram over its label sets."""
    snap = rec.get("obs")
    if not snap:
        return None
    hs = _select(snap["histograms"], name)
    if not hs:
        return None
    return (float(sum(h["sum"] for h in hs)),
            int(sum(h["count"] for h in hs)))


def module_seconds(rec: dict, module: str) -> float | None:
    """Device seconds, per chip, of one jitted program by name."""
    tr = rec.get("trace")
    if not tr:
        return None
    t = tr["modules"].get(module)
    return t if t else None


def roofline_pct(rec: dict, bytes_needed: float, seconds: float | None
                 ) -> float | None:
    """Share of the HBM roofline: the least time the chip's published
    bandwidth allows for ``bytes_needed``, over ``seconds``."""
    peaks = rec.get("peaks")
    if not peaks or not seconds or not bytes_needed:
        return None
    return 100.0 * bytes_needed / peaks["hbm_bytes_per_s"] / seconds


def idle_pct(rec: dict) -> float | None:
    tr = rec.get("trace")
    if not tr or not tr["devices"] or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
