"""The bytes a verification needs, from counts alone.

Per signature verified on the device: its 32-byte key, its 64-byte
signature, its sign-bytes and the one-byte verdict that comes back.
Counted from what the *problem* needs — not from lanes, limbs, padding
or the key tables' layout — so the number reads the same whatever
implements the kernel.  ``least_seconds`` is the time the chip's memory
system needs to move them once at its published peak; the v5e publishes
no integer-vector peak, so no compute bound is claimed and a kernel's
roofline share here is a share of the memory bound only.
"""

from __future__ import annotations

import json
import os

KEY_BYTES = 32
SIG_BYTES = 64
VERDICT_BYTES = 1


def verify_bytes(n_sigs: int, sign_bytes_total: int) -> int:
    """Bytes to verify ``n_sigs`` signatures whose sign-bytes are
    ``sign_bytes_total`` long in all."""
    return n_sigs * (KEY_BYTES + SIG_BYTES + VERDICT_BYTES) + sign_bytes_total


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown ``device_kind`` is an
    error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"{path}; add the row with its source"
        )
    return table[device_kind]


def least_seconds(n_bytes: int, device_kind: str) -> float:
    return n_bytes / peaks(device_kind)["hbm_bytes_per_s"]
