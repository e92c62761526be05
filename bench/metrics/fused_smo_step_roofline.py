"""Share of its roofline that ``fused_smo_step`` reaches, in percent: the
least time the chip could take for the traced calls (their work counted
from the cell's shapes by ``roofline.fused_smo_step``, at the chip's
published peaks) over the calls' device time in the trace.

The kernel's ``pallas_call`` carries no ``name=`` yet; its device events
are the custom calls that XLA names after the jitted wrapper,
``fused_smo_step.<n>``."""
import devtrace
import roofline

KERNEL = "fused_smo_step"


def read(run):
    if run.trace is None:
        return None
    secs, calls = devtrace.ops_matching(run.trace, KERNEL)
    if not calls or secs <= 0:
        return None
    flops, nbytes = roofline.fused_smo_step(run.cfg["rows"],
                                            run.cfg["features"])
    least, _ = roofline.least_time(flops, nbytes, run.device_kind)
    return 100.0 * calls * least / secs
