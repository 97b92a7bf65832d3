"""Roofline share of the forward block kernels of ``fno3d-ns`` in serving:
the blocks' least time per request at the kept bins
(``ns3d_work.least_seconds``: the larger of operations over peak FLOP/s
and least bytes over HBM bandwidth) times the requests of the traced
window, over the device time of every Mosaic kernel event in it, in %."""
import ns3d_work


def read(view):
    c, t = view.counters, view.trace
    if t is None or c.get("training") or not c.get("samples"):
        return None
    kernel_s = t.kind_s["kernel"] * t.chips
    if kernel_s <= 0:
        return None
    passes = c["samples"] / c["batch"]
    least = ns3d_work.least_seconds(view.run.cfg, c["batch"], view.peak)
    return 100.0 * passes * least["seconds"] / kernel_s
