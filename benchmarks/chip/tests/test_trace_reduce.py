"""The trace reduction on a recorded trace: two decode ticks of zamba2-2.7b
on a v5e chip (the exploratory call of the benchmark's first PR), cut to the
ops of 20 us or more, every kernel op, the device programs and the
harness's ``bench.tick`` spans."""

from __future__ import annotations

import os

import pytest

import chipfixtures  # noqa: F401  (the benchmark on the path)
import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "zamba2_decode_2ticks.xplane.pb")


def test_recorded_trace():
    r = trace_reduce.reduce_xplane(FIXTURE)
    assert r.n_devices == 1
    assert 0.45 < r.window_s < 0.46              # the two ticks, end to end
    assert 0 < r.busy_s < r.window_s
    # 9 shared-attention layers a tick, one paged-attention call each
    assert r.kernel_calls["paged_decode_attention"] == 18
    assert r.kernel_ns["paged_decode_attention"] / 1e9 < r.busy_s
    assert "%while" not in r.op_ns             # spans its body's ops
    b = r.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == "bench.tick after jit_decode_step"


@pytest.mark.parametrize("loop", ["%while.1 = loop", "%while"])
def test_union_and_gaps_by_hand(loop):
    events = {"dev0": [(loop, 0.0, 100.0),
                       ("%fusion.1 = a", 10.0, 20.0),
                       ("%repro_kernel.ssd.3 = b", 25.0, 15.0),
                       ("%repro_kernel.ssd = c", 70.0, 10.0)],
              "dev1": [("%copy.2 = d", 0.0, 50.0)]}
    mods = {"dev0": [("jit_step(1)", 0.0, 45.0), ("jit_other(2)", 60.0, 30.0)]}
    spans = [("bench.tick", 0.0, 100.0)]
    r = trace_reduce.reduce_events(events, spans, (0.0, 100.0), mods)
    # dev0: the while covers 0-100; dev1 busy 0-50
    assert r.busy_ns == (100.0 + 50.0) / 2
    assert r.kernel_ns == {"ssd": 25.0} and r.kernel_calls == {"ssd": 2}
    assert r.op_ns["%fusion"] == 20.0 and "%while" not in r.op_ns
    r = trace_reduce.reduce_events({"dev0": events["dev0"][1:]}, spans,
                                   (0.0, 100.0), mods)
    gaps = dict(r.breakdown()["idle_gaps"])
    assert gaps["bench.tick inside jit_step"] == 10e-9
    assert gaps["bench.tick after jit_step"] == 30e-9
    assert gaps["bench.tick after jit_other"] == 20e-9
