"""Share of the roofline reached by the climb's gain sweep
(``kernels/gain_scan.py`` ``_gain_kernel``): the least time its bytes
and operations (``harness/roofline.gain_kernel``) allow on this device,
over its device time in the trace.

The trace names the kernel by its HLO text, a Mosaic custom call; the
climb (``ls_device_climb`` span) runs no other. Each call is sized by
the span it ran in: its rows and tasks."""
from harness import roofline, trace


def read(run):
    if run.trace is None:
        return None
    climbs = [(sp.t0, sp.t1, sp.attrs) for sp in run.spans
              if sp.name == "ls_device_climb"]
    mu = run.config["planner"]["mu"]
    nbytes = nops = seconds = 0.0
    for s, e in run.trace.spans_of(trace.KERNEL_MARK):
        attrs = next((a for t0, t1, a in climbs if t0 <= s < t1), None)
        if attrs is None:
            continue
        b, o = roofline.gain_kernel(attrs["rows"], attrs["N"], mu)
        nbytes += b
        nops += o
        seconds += e - s
    if not seconds:
        return None
    peak = roofline.peak(run.device_kind)
    least = max(nbytes / peak["bytes_per_s"], nops / peak["flops_per_s"])
    return 100.0 * least / seconds
