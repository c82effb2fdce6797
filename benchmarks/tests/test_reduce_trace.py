"""(c) The trace reducer on synthetic interval lists."""

import pytest

from benchmarks.harness import reduce_trace as rt

MS = 1_000_000
OPS = [("fusion.1", 0, 4 * MS), ("fusion.2", 2 * MS, 4 * MS),      # overlap: 0..6
       ("while", 10 * MS, 10 * MS), ("dot.3", 11 * MS, 3 * MS),     # nested in the while
       ("dot.3", 15 * MS, 3 * MS), ("copy", 30 * MS, 2 * MS)]
MODULES = [("jit__prefill_sample_fn(11)", 0, 6 * MS), ("jit_fn(22)", 10 * MS, 10 * MS),
           ("jit_fn(22)", 30 * MS, 2 * MS), ("jit_fn(22)", 40 * MS, 4 * MS),
           ("jit__place(5)", 50 * MS, 1 * MS)]


def test_overlapping_and_nested_ops_union_to_the_busy_time():
    assert rt.busy_ns(OPS) == (6 + 10 + 2) * MS
    assert rt.span_ns(OPS) == 32 * MS
    assert rt.idle_share(OPS) == pytest.approx(1 - 18 / 32)
    assert rt.busy_ns([]) == 0 and rt.idle_share([]) is None


def test_self_time_takes_nested_ops_out_of_their_parent():
    nested = OPS[2:]  # on a device line events follow or contain each other
    by = rt.self_time_by_name(nested)
    assert by == {"while": 4 * MS, "dot.3": 6 * MS, "copy": 2 * MS}
    assert rt.top_by_time(nested, 2) == [["dot.3", 0.006], ["while", 0.004]]


def test_module_medians_by_regex():
    assert rt.module_median_ms(MODULES, r"^jit_fn(\(|$)") == 4.0
    assert rt.module_median_ms(MODULES, r"^jit__prefill_") == 6.0
    assert rt.module_median_ms(MODULES, r"^jit_nothing") is None
    assert rt.base_name("jit_fn(22)") == "jit_fn"
    assert rt.base_name("%fusion.3395 = f32[9732096]{0:T(1024)} fusion(f32[64,152064]{1,0} "
                        "%custom-call.183), kind=kCustom") == "fusion.3395 f32[9732096]"
    assert rt.base_name("%sort.26 = (s32[9732096]{0}, s32[9732096]{0}) sort(s32[9] %r)") \
        == "sort.26 s32[9732096]"


def test_gaps_are_labelled_by_the_programs_around_them():
    gaps = rt.idle_gaps(OPS, MODULES, n=2)
    assert [(g[0], g[1]) for g in gaps] == [(20 * MS, 10 * MS), (6 * MS, 4 * MS)]
    assert gaps[0][2] == "after:jit_fn|before:jit_fn"
    assert gaps[1][2] == "after:jit__prefill_sample_fn|before:jit_fn"
