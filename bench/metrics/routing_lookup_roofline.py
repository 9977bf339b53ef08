"""Share of its HBM roofline that ``routing_lookup`` reached in the traced
window, %: the bytes its calls must move (``kernels/routing_lookup.py``, from
their shapes) at the chip's peak bandwidth, over the device time of its
program's events. Bytes bound it: the published peaks give no rate for its
integer work."""

KERNEL = "routing_lookup"
PROGRAM = "jit__routing_lookup"


def read(run):
    if run.summary is None:
        return None
    calls, seconds = run.summary.program_seconds(PROGRAM)
    if not calls or seconds <= 0:
        return None
    need = calls * run.kernel_work(KERNEL)(run.kernel_shapes[KERNEL])
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
