"""Plain integer oracle of the SyncAgtr device lane, in numpy alone.

The device lane's fixed point: the fp32 product ``g * 10**precision``
rounded half to even, saturating at the SAT range with sticky overflow
sentinels; the Get reply is the int32 register times the fp32 reciprocal
of the scale. Copied from ``chip_smoke.py`` (``quantize_np``,
``dequant_np``) with the constants written out, so the check imports
nothing of the program.
"""
from __future__ import annotations

import numpy as np

INT32_MAX = 2 ** 31 - 1
INT32_MIN = -(2 ** 31 - 1)
SAT_MAX = INT32_MAX - 1
SAT_MIN = INT32_MIN + 1


def quantize(f: np.ndarray, scale) -> np.ndarray:
    """fp32 values -> fixed-point registers (int64, sentinels outside)."""
    y = np.rint(np.asarray(f, np.float32) * np.float32(scale))
    q = np.clip(y, SAT_MIN, SAT_MAX).astype(np.int64)
    q[y > SAT_MAX] = INT32_MAX
    q[y < SAT_MIN] = INT32_MIN
    return q


def dequantize(regs: np.ndarray, scale) -> np.ndarray:
    inv = np.float32(1.0) / np.float32(scale)
    return np.asarray(regs).astype(np.float32) * inv


def reply_f32(chunk: np.ndarray, scale) -> np.ndarray:
    """The Get reply of one ``clear="copy"`` call: its own chunk, through
    the register and back."""
    return dequantize(quantize(chunk, scale), scale)


def mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Elements that differ (a shape or dtype mismatch counts them all)."""
    got = np.asarray(got)
    if got.shape != want.shape or got.dtype != want.dtype:
        return int(want.size)
    return int(np.count_nonzero(got != want))
