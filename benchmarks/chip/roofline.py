"""The table of peaks and the bytes a query needs: the roofline's two inputs.

``min_bytes`` depends on the query and the data only — never on the kernel
variant, the padding or what the program chose to stage: real series
selected x real samples in (start - window, end] x buckets x 4 B (the f32
the store serves). The kernels are bandwidth-bound by this count (a few
operations per value read). A kernel that reads only the window edges would
make the count stale: that needs a ``benchmark`` PR first.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12},
}
VALUE_BYTES = 4


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       "benchmarks/chip/roofline.py with its source")
    return PEAKS[device_kind]


def min_bytes(data, start_ms: int, end_ms: int, window_ms: int) -> int:
    """Bytes one query over [start, end] with ``window_ms`` look-back has to
    read of ``data`` (every series is selected in these cells)."""
    return data.samples_in(start_ms - window_ms, end_ms) * data.buckets * VALUE_BYTES


def min_seconds(n_bytes: float, device_kind: str) -> float:
    return n_bytes / peaks(device_kind)["hbm_bytes_per_s"]
