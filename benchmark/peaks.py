"""Published peaks of the devices the benchmark runs on, keyed by
``device_kind`` as jax reports it.  A device that is not here is an
error, never a default.

No metric divides by these yet: no jitted step of the program carries
a ``jax.named_scope``, so no kernel can be found in a trace after a
refactor, and kernel time against the roofline is "not measured".
The table is here so that the PR that names the kernels only adds the
functions that compute each kernel's operations and bytes.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": system architecture table
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2 ** 30,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def for_device(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"row with its source to benchmark/peaks.py") from None

