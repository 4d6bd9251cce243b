"""Share of the device's busy time spent in the full-residual check, in %.

The check's matvec is its own jitted program (``kernel_matvec_pallas``,
called by ``core/krr.py`` ``residual_report``); the step's row-block matvec
is inlined in the step's program.  So the trace tells them apart by the
program that launched the kernel.  Layer: solvers (``core/askotch.py`` ->
``core/krr.py``)."""

from metrics.work import share

CHECK_PROGRAM = "jit_kernel_matvec_pallas"


def read(rec):
    s = rec["summary"]
    if s is None or CHECK_PROGRAM not in s.program_s:
        return None
    return share(s.program_s[CHECK_PROGRAM], s.busy_s)
