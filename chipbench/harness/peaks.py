"""Published peaks of one chip, keyed by jax's `device_kind`.

A device that is not in the table is an error, never a default: a rate
over the wrong peak is a wrong number under a device metric's name.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture table):
    # 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s. The v5e reports
    # device_kind 'TPU v5 lite' (chip run, PR 21).
    'TPU v5 lite': {'bf16_flops_per_s': 197e12,
                    'hbm_bytes_per_s': 819e9},
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise SystemExit(
            'chipbench: no published peaks for device_kind %r (have %r); '
            'add the device with its source to chipbench/harness/peaks.py'
            % (device_kind, sorted(PEAKS)))
    return PEAKS[device_kind]


def roofline(cost, peaks):
    """(least seconds the chip could take, which bound applies) for what a
    kernel requires: `cost` is (FLOPs, bytes moved to and from HBM)."""
    flops, nbytes = cost
    t_flops = flops / peaks['bf16_flops_per_s']
    t_bytes = nbytes / peaks['hbm_bytes_per_s']
    return max(t_flops, t_bytes), 'flops' if t_flops >= t_bytes else 'bytes'
