"""The yardstick's arithmetic that every architecture shares: chip peaks
and the roofline.  The work a model needs (FLOPs and bytes from shapes and
token counts) is its architecture's own file,
`chipbench/architectures/<a>/costs.py`.  Kept here, and not taken from
pathway_tpu/internals/costmodel.py, so that a later PR can change the
program and not the yardstick.

Peaks: Google Cloud TPU documentation, "System architecture" page of each
TPU version (peak bf16 compute, HBM bandwidth and capacity per chip),
keyed by the `device_kind` JAX reports.  A device that is not in the table
is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    # device_kind: bf16 FLOP/s, HBM bytes/s, HBM bytes
    "TPU v5 lite": {  # cloud.google.com/tpu/docs/v5e
        "flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
    "TPU v4": {  # cloud.google.com/tpu/docs/v4
        "flops": 275e12,
        "hbm_bytes_per_s": 1228e9,
        "hbm_bytes": 32e9,
    },
    "TPU v5": {  # cloud.google.com/tpu/docs/v5p
        "flops": 459e12,
        "hbm_bytes_per_s": 2765e9,
        "hbm_bytes": 95e9,
    },
    "TPU v6 lite": {  # cloud.google.com/tpu/docs/v6e
        "flops": 918e12,
        "hbm_bytes_per_s": 1640e9,
        "hbm_bytes": 32e9,
    },
}


class UnknownDevice(LookupError):
    pass


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise UnknownDevice(
            f"device_kind {device_kind!r} has no entry in chipbench/costs.py "
            f"PEAKS (known: {sorted(PEAKS)}); add its published peaks with "
            "their source before reporting a roofline or MFU on it"
        )
    return PEAKS[device_kind]


DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def dtype_bytes(name: str) -> int:
    """Bytes an element of the named type takes on the chip (a store's
    `index_dtype`).  A type that is not in the table is an error."""
    if name not in DTYPE_BYTES:
        raise LookupError(
            f"dtype {name!r} has no entry in chipbench/costs.py DTYPE_BYTES "
            f"(known: {sorted(DTYPE_BYTES)})"
        )
    return DTYPE_BYTES[name]


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> dict:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s, and which of the two binds."""
    p = peaks(device_kind)
    compute_s = flops / p["flops"]
    memory_s = nbytes / p["hbm_bytes_per_s"]
    return {
        "seconds": max(compute_s, memory_s),
        "bound": "compute" if compute_s >= memory_s else "memory",
    }
