"""Published peaks, keyed by ``device_kind`` (substring, checked in order).

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
chip-to-chip) and the v4 / v5p / v6e pages for the others. Copied from
``distributed_tensorflow_tpu/utils/flops.py`` so that a later PR which
changes the program cannot change the yardstick. A device that is not in
the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = (
    # (device_kind substring, bf16 FLOP/s, HBM bytes/s, HBM bytes)
    ("v6e", 918e12, 1640e9, 32e9),
    ("v6", 918e12, 1640e9, 32e9),
    ("v5p", 459e12, 2765e9, 95e9),
    ("v5 lite", 197e12, 819e9, 16e9),
    ("v5litepod", 197e12, 819e9, 16e9),
    ("v5e", 197e12, 819e9, 16e9),
    ("v4", 275e12, 1228e9, 32e9),
)


def lookup(device_kind: str) -> dict:
    kind = device_kind.lower()
    for sub, flops, bw, hbm in PEAKS:
        if sub in kind:
            return {"bf16_flops": flops, "hbm_bytes_per_s": bw, "hbm_bytes": hbm}
    raise ValueError(
        f"no peaks for device_kind {device_kind!r}: add it to "
        "benchmarks/peaks.py with its source")
