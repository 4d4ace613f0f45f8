"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip.  Utilisation and
roofline shares are taken against the bf16 peak even though the
configurations compute in float32 at ``"highest"`` precision: the bf16
peak is what the chip can do, so the share says how far the program is
from it.  A device not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM",
    },
}


def peak_for(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}") from None
