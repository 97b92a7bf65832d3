"""The trace reduction on small recorded event lists with known answers."""
import pytest

import onchip_testkit  # noqa: F401
import devtrace as tr
from devtrace import Event

HLO = """
HloModule jit_step
ENTRY %main {
  %fused_fnond_call.4 = f32[64,64,128,128]{3,2,1,0:T(8,128)} custom-call(%fusion.19, %bitcast.79), custom_call_target="tpu_custom_call", backend_config={}
  %custom-call.3 = f32[128,128,32,32]{1,3,2,0:T(8,128)S(1)} custom-call(%slice-done.12), custom_call_target="ConcatBitcast"
  %all-reduce.1 = f32[8,64]{1,0} all-reduce(f32[8,64]{1,0} %x), replica_groups={}
  %all-gather-start = (f32[4]{0}, f32[8]{0}) all-gather-start(%z)
  ROOT %fusion.7 = (f32[2]{0}, u32[]{:S(2)}) fusion(%a), kind=kLoop, calls=%fc
}
"""


def _ev(text, start, end):
    return Event(text, start, end)


def test_hlo_kinds_find_kernels_and_collectives():
    kinds = tr.hlo_kinds([HLO])
    assert kinds["fused_fnond_call.4"] == "kernel"
    assert kinds["custom-call.3"] == "xla"
    assert kinds["all-reduce.1"] == "collective"
    assert kinds["all-gather-start"] == "collective"
    assert kinds["fusion.7"] == "xla"


@pytest.mark.parametrize("text,kind", [
    ("%fused_fnond_call.4 = f32[64]{0} custom-call(%a)", "kernel"),
    ("%reduce-scatter.9 = f32[4]{0} reduce-scatter(f32[8]{0} %b)",
     "collective"),
    ("%copy.26 = bf16[64]{0} copy(f32[64]{0} %c)", "xla"),
    ("%new_kernel.1 = f32[8]{0} custom-call(%d), "
     "custom_call_target=\"tpu_custom_call\"", "kernel"),
])
def test_events_are_classified_by_instruction(text, kind):
    assert tr.classify(text, tr.hlo_kinds([HLO])) == kind


def test_reduce_gives_busy_idle_and_kinds():
    kernel = "%fused_fnond_call.4 = f32[64]{0} custom-call(%a)"
    fusion = "%fusion.7 = f32[2]{0} fusion(%a)"
    coll = "%all-reduce.1 = f32[8]{0} all-reduce(%x)"
    chip0 = [_ev(kernel, 1, 3), _ev(fusion, 2, 4), _ev(coll, 6, 7)]
    chip1 = [_ev(kernel, -1, 1), _ev(fusion, 8, 12)]
    spans = [Event("step dispatch", 0, 5), Event("loss sync", 4, 6),
             Event("loss sync", 7, 10)]
    r = tr.reduce([chip0, chip1], spans, (0.0, 10.0), tr.hlo_kinds([HLO]))
    # chip 0 busy [1,4] + [6,7] = 4 s; chip 1 busy [0,1] + [8,10] = 3 s.
    assert r.window_s == 10.0 and r.chips == 2
    assert r.busy_s == pytest.approx(3.5)
    assert r.idle_share == pytest.approx(0.65)
    assert r.kind_s["kernel"] == pytest.approx((2 + 1) / 2)
    assert r.kind_s["xla"] == pytest.approx((2 + 2) / 2)
    assert r.kind_s["collective"] == pytest.approx(1 / 2)
    assert r.op_s["fused_fnond_call.4"] == pytest.approx(1.5)
    # Idle: chip 0 [0,1] (step dispatch), [4,6] (loss sync, the shorter
    # span at 5), [7,10] (loss sync); chip 1 [1,8] (mid 4.5: loss sync).
    assert r.idle_by_span["step dispatch"] == pytest.approx(1 / 2)
    assert r.idle_by_span["loss sync"] == pytest.approx((2 + 3 + 7) / 2)
    assert sum(r.idle_by_span.values()) == pytest.approx(10 - 3.5)


def test_union_and_top():
    assert tr.union([(3, 4), (0, 2), (1, 3), (5, 5)]) == [(0, 4)]
    assert tr.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                          ["c", 2.0]]


def test_window_comes_from_the_window_span():
    spans = [Event("window", 2.0, 9.0), Event("pump", 3.0, 4.0)]
    assert tr.window_of(spans) == (2.0, 9.0)
    assert tr.window_of(spans[1:]) is None
