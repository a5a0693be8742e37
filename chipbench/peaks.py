"""Peak rates of the chips the benchmark runs on, keyed by ``device_kind``.

A device that is not in the table is an error: there is no default and no
CPU entry, so no number is ever divided by a peak the run did not have.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(RuntimeError):
    pass


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peak table entry for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
