"""Model FLOP/s utilization of the window: samples per second times the
layer algebra's operations per sample (``work.model_flops``, training or
forward), over the chips' bf16 peak, in %."""
import work


def mfu(view, training):
    c = view.counters
    if c.get("training") != training or not c.get("samples"):
        return None
    rate = c["samples"] / c["window_s"]
    ops = work.model_flops(view.run.cfg, 1, training=training)
    return 100.0 * rate * ops / (view.chips * view.peak["flops_per_s"])
