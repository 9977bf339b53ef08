"""Work of one ``routing_lookup`` dense refresh (the Pallas route kernel)
from its shapes: ``keys`` key ids against a ``table`` of padded entries."""

INT32 = 4


def hbm_bytes(shape: dict) -> float:
    """Bytes the refresh must move: the key ids in, one destination per
    key out, and the table's keys and destinations in once."""
    return 2 * shape["keys"] * INT32 + 2 * shape["table"] * INT32
