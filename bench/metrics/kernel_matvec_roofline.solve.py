"""The fused kernel matvec's share of its roofline over the window, in %:
the least time its calls need at the chip's published peaks, over the
device time of its operations.  The calls are counted from the solves: one
(n x n) check per history record and one (b x n) row block per iteration.
Layer: kernels (``kernels/kernel_matvec.py``)."""

from metrics import work


def read(rec):
    s = rec["summary"]
    if s is None or not rec.get("iters"):
        return None
    n, d, t, b, kernel = rec["n"], rec["d"], rec["t"], rec["b"], rec["kernel"]
    least = 0.0
    for m, calls in ((n, sum(rec["checks"])), (b, sum(rec["iters"]))):
        least += calls * work.least_seconds(
            work.matvec_flops(m, n, d, t, kernel), work.matvec_bytes(m, n, d, t),
            rec["device_kind"])
    secs = work.kernel_seconds(s)
    return work.share(least, secs) if secs > 0 else None
