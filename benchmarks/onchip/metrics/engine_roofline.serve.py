"""Roofline share of the engine's forward block kernels in serving, in %."""
import _roofline


def read(view):
    return _roofline.share(view, training=False)
