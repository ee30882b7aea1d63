"""The reduction from a profiler trace to the per-layer numbers."""

import pytest

from bench import tracing
from tinycell import FIXTURES


def test_union_merges_overlaps_and_touching_intervals():
    got = tracing.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)])
    assert got == [(0, 4), (5, 7), (10, 11)]


def test_op_name_strips_the_instruction_number():
    assert tracing.op_name("%stacked_mean_linear_pallas.2 = f32[6,25600,64] "
                           "custom-call(s32[6] %a)") == "stacked_mean_linear_pallas"
    assert tracing.op_name("%fusion = f32[2] fusion(f32[2] %x)") == "fusion"
    assert tracing.is_kernel("%k.1 = f32[2] custom-call(f32[2] %x)")
    assert not tracing.is_kernel("%fusion.3 = f32[2] fusion(f32[2] %x)")


def _events():
    """Two devices; a window annotation from 100 to 200 ns; host frames of
    the program's own files and of others."""
    k = "%{}.1 = f32[8] custom-call(f32[8] %x)"
    f = "%fusion.{} = f32[8] fusion(f32[8] %x)"
    return {
        "devices": [
            [(90, 110, k.format("agg")), (105, 120, f.format(1)),
             (150, 160, k.format("agg")), (190, 230, f.format(2))],
            [(100, 200, k.format("agg"))],
        ],
        "python": [
            (0, 300, "$session.py:10 fit"),
            (100, 200, "bench.window"),
            (118, 149, "$staging.py:20 gather"),
            (120, 140, "$<unknown> copy"),
            (161, 189, "$profiler.py:5 wrapper"),
        ],
    }


def test_idle_share_kernel_time_and_gaps_on_a_hand_built_trace():
    own = frozenset({"session.py:fit", "staging.py:gather", "profiler.py:HotnessProfile"})
    s = tracing.reduce(_events(), "bench.window", own)
    assert s["window_s"] == pytest.approx(100e-9)
    # device 0 busy [100,120] + [150,160] + [190,200] = 40; device 1: 100
    assert s["busy_s"] == pytest.approx((40 + 100) / 2 * 1e-9)
    assert s["devices"] == 2
    # kernel 'agg': device 0 clipped-in events 20 + 10 ns (whole durations),
    # device 1 100 ns; averaged over devices
    assert s["kernel_s"]["agg"] == pytest.approx((20 + 10 + 100) / 2 * 1e-9)
    assert s["kernel_calls"]["agg"] == pytest.approx(3 / 2)
    gaps = dict(s["idle_gaps"])
    # device 0 idles over [120,150]: the staging frame until 149, then
    # session.py's fit; and over [160,190], under a function that is not
    # the program's in a file whose name one of the program's files shares,
    # so the innermost own frame is session.py's fit
    assert gaps["staging.py:gather"] == pytest.approx(29e-9)
    assert gaps["session.py:fit"] == pytest.approx(31e-9)


def test_functions_of_names_each_function_class_and_module(tmp_path):
    f = tmp_path / "profiler.py"
    f.write_text("class HotnessProfile:\n    def fit(self):\n        pass\n\n"
                 "async def fetch():\n    pass\n")
    assert tracing.functions_of([f]) == {
        "profiler.py:<module>", "profiler.py:HotnessProfile",
        "profiler.py:fit", "profiler.py:fetch"}


def test_a_missing_window_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce(_events(), "bench.nothing")


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e: three calls of a jitted function (a
    Pallas kernel ``tiny`` and a fusion) inside ``bench.window``, with
    10 ms sleeps between them."""
    events = tracing.load(FIXTURES / "tiny.xplane.pb")
    assert len(events["devices"]) == 1
    assert len(events["devices"][0]) == 6
    s = tracing.reduce(events, "bench.window")
    ops = [(a, b) for a, b, _ in events["devices"][0]]
    t0, t1 = next((a, b) for a, b, n in events["python"] if n == "bench.window")
    inside = [(max(a, t0), min(b, t1)) for a, b in ops if b > t0 and a < t1]
    assert s["busy_s"] == pytest.approx(sum(b - a for a, b in inside) * 1e-9)
    assert 0.99 < 1 - s["busy_s"] / s["window_s"] < 1.0
    assert set(s["kernel_s"]) == {"tiny"}
    assert dict(s["device_ops"])["fusion"] > dict(s["device_ops"])["tiny"] > 0
