"""Work of one ``_interval_step_add`` call (the add-mode fused step) from
its shapes: ``window`` + 1 ring columns over ``keys`` rows of int32."""

INT32 = 4


def hbm_bytes(shape: dict) -> float:
    """Bytes the step must move: the two state planes in and out, the
    per-key counts in, and its four per-key outputs (window and slot
    totals before the update, held slots and held sum after it)."""
    w1, d1 = shape["window"] + 1, shape["keys"]
    planes = 2 * w1 * d1 * INT32
    return 2 * planes + d1 * INT32 + 4 * d1 * INT32 + 2 * w1 * INT32
