"""The ms of the window in which the pause witness (``witness.py``) saw the
machine not run: every overshoot of its 1 ms sleep by ``PAUSE_MIN_S`` or
more, clipped to the window. A total: 0 is a reading."""

import witness


def read(ctx):
    return 1e3 * witness.clipped_total(ctx.machine.pauses, *ctx.window)
