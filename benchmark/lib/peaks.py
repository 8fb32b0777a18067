"""Published per-chip peaks, keyed by JAX's ``device_kind``.  A copy of
``helix_tpu/device/peaks.py`` (PERF.md, Open questions: the original is the
program's; the benchmark's shares are taken against this table so that no
later PR can move them).  A device that is not here is an error."""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 10**9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def chip_peaks(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
