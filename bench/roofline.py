"""Peaks of each device kind, and the bytes a lookup has to move.

The byte count depends only on the batch's shape and the built index's
own declared error bound, never on what an implementation happens to
read, so no correct implementation can read above 100% of it.
"""

from __future__ import annotations

import math

# Per-chip peaks.  Source: Google Cloud documentation, "TPU v5e".
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}

KEY_BYTES = 8        # the query key in: its exact image, two f32 words
MODEL_ROW_BYTES = 8  # one linear model row: slope and intercept, f32 each
PROBE_BYTES = 8      # one slot key probed in the error window
PAYLOAD_BYTES = 8    # the stored record locator read
ANSWER_BYTES = 9     # the answer out: locator and found flag


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}"
                       ) from None


def probes(err_bound: float) -> int:
    """Slot keys a search must probe inside a window of 2E+1 slots."""
    return max(1, math.ceil(math.log2(2.0 * float(err_bound) + 1.0)))


def lookup_bytes(n_keys: int, err_bound: float) -> int:
    """Bytes no correct exact lookup of ``n_keys`` keys can avoid, for an
    index whose declared maximum prediction error is ``err_bound``."""
    per_key = (KEY_BYTES + MODEL_ROW_BYTES + probes(err_bound) * PROBE_BYTES
               + PAYLOAD_BYTES + ANSWER_BYTES)
    return int(n_keys) * per_key


def hbm_roofline_pct(n_bytes: int, device_seconds: float,
                     device_kind: str) -> float | None:
    """Share (%) of the least time the bytes need at peak bandwidth in the
    device time measured; None where no device time was measured."""
    if device_seconds <= 0:
        return None
    least = n_bytes / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / device_seconds
