"""The work a kernel call needs, counted from the problem's shapes, and the
chip's published peaks.

A fused kernel matvec ``K(a, x) @ v`` with ``a`` (m, d), ``x`` (n, d) and
``v`` (n, t) needs, per kernel entry: 2d operations for the distance (or
inner product), the kernel map, and 2t for the product with ``v``.  Its
least traffic is reading ``a``, ``x`` and ``v`` once and writing the (m, t)
result.  The tile's padding (d to 32, t to 128 lanes, m to the row tile) is
never counted, so a re-tiled or replaced kernel reads the same work and its
share cannot pass 100 % from a miscount.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "peaks.json")

#: operations per kernel entry of the kernel map, on top of the distance:
#: the squared-L2 expansion |a|^2 + |x|^2 - 2 a.x adds 3, the rbf map a
#: scale and an exp (a transcendental counts as one operation)
MAP_OPS = {"rbf": 3 + 2}


def matvec_flops(m: int, n: int, d: int, t: int, kernel: str = "rbf") -> float:
    """Operations K(a, x) @ v needs: m n entries of 2d + map + 2t."""
    return float(m) * n * (2 * d + MAP_OPS[kernel] + 2 * t)


def matvec_bytes(m: int, n: int, d: int, t: int, itemsize: int = 4) -> float:
    """Least HBM traffic of K(a, x) @ v: a, x and v read once, out written."""
    return float(itemsize) * (m * d + n * d + n * t + m * t)


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device missing from the
    table is an error, never a default."""
    with open(PEAKS_FILE) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"{PEAKS_FILE}; known: {sorted(table)}"
        )
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The roofline: the larger of operations over peak operations per
    second and bytes over peak bytes per second."""
    p = peaks(device_kind)
    return max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])


#: the fused matvec tile, as its operations are named in the device trace
#: (``%kernel_matvec_pallas.<k>``, a ``tpu_custom_call``)
MATVEC_KERNEL = "%kernel_matvec_pallas"


def kernel_seconds(summary, kernel: str = MATVEC_KERNEL) -> float:
    """Device seconds of the operations of one Pallas kernel in the window,
    in whichever program launched them."""
    return sum(s for ops in summary.program_op_s.values()
               for name, s in ops.items() if name == kernel or name.startswith(kernel + "."))


def share(part: float, whole: float):
    """100 * part / whole, or None where the whole is not positive."""
    return 100.0 * part / whole if whole > 0 else None
