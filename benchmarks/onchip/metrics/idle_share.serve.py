"""Device idle share of the traced window, in %."""
import _idle


def read(view):
    return _idle.idle(view)
