"""Served forward's model FLOP/s utilization, in %."""
import _mfu


def read(view):
    return _mfu.mfu(view, training=False)
