"""Share of its HBM roofline that ``interval_step_add`` reached in the traced
window, %: the bytes its calls must move (``kernels/interval_step_add.py``, from
their shapes) at the chip's peak bandwidth, over the device time of its
program's events. Bytes bound it: the published peaks give no rate for its
integer work."""

KERNEL = "interval_step_add"
PROGRAM = "jit__interval_step_add"


def read(run):
    if run.summary is None:
        return None
    calls, seconds = run.summary.program_seconds(PROGRAM)
    if not calls or seconds <= 0:
        return None
    need = calls * run.kernel_work(KERNEL)(run.kernel_shapes[KERNEL])
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
