"""Model FLOP/s utilization of served ``fno3d-ns``: samples per second
times the model's operations per sample at the kept bins
(``ns3d_work.model_flops``: the blocks, the one-layer lift and the
projection), over the chips' bf16 peak, in %."""
import ns3d_work


def read(view):
    c = view.counters
    if c.get("training") or not c.get("samples"):
        return None
    rate = c["samples"] / c["window_s"]
    ops = ns3d_work.model_flops(view.run.cfg, 1)
    return 100.0 * rate * ops / (view.chips * view.peak["flops_per_s"])
