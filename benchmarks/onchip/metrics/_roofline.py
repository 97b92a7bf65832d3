"""The FNO blocks' share of their roofline: the least time the chip could
take for the window's block work (``work.least_seconds`` of one pass, a
training step or a served request, times the passes in the window: the
larger of operations over peak FLOP/s and least bytes over HBM
bandwidth, with the weights read once per pass) over the device time of
every Mosaic kernel event in the traced window, summed over the chips,
in %."""
import work


def share(view, training):
    c, t = view.counters, view.trace
    if t is None or c.get("training") != training or not c.get("samples"):
        return None
    kernel_s = t.kind_s["kernel"] * t.chips
    if kernel_s <= 0:
        return None
    passes = c["samples"] / c["batch"]
    least = work.least_seconds(view.run.cfg, c["batch"], view.peak,
                               training=training)
    return 100.0 * passes * least["seconds"] / kernel_s
