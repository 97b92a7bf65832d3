"""Share of the traced window in which no op ran on the device: one minus
the union of the op intervals over the window, averaged over the chips,
in %."""


def idle(view):
    t = view.trace
    if t is None or t.window_s <= 0 or t.n_events == 0:
        return None
    return 100.0 * t.idle_share
