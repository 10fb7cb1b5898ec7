"""A smoke-size pass of each serving cell's driver on the CPU, with the
Pallas kernels in interpret mode: set-up, the open-loop window, the result
object and the reference comparison."""

from __future__ import annotations

import time

import pytest

from chipfixtures import interpret, tiny_bench  # noqa: F401
import serve_driver


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct(tiny_bench, interpret, tmp_path, trace):
    res = serve_driver.run_cell(
        tiny_bench, tiny_bench.cell("tiny-ssm-chat"), seed=2**31 + 7,
        seconds=4.0, trace=trace, t_start=time.perf_counter(),
        trace_dir=str(tmp_path / "trace"), kernel_mode=interpret)
    assert res["correct"], res["compared"]
    assert res["attempted"] == 48 and res["failed"] == 0   # 12/s for 4 s
    assert res["device"]["platform"] == "cpu"
    if trace:
        assert {"host_ms_per_tick", "slot_occupancy", "mfu.prefill",
                "mfu.decode"} <= set(res["metrics"])
        assert "window_s" in res["device"] and "breakdown" in res
    else:
        names = {m["name"] for m in tiny_bench.spec["end_to_end"]}
        assert set(res["metrics"]) == names
        assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "compared"


def test_nothing_compiles_in_the_window(tiny_bench, interpret):
    cell = tiny_bench.cell("tiny-ssm-chat")
    cfg = tiny_bench.config(cell["config"])
    mix = tiny_bench.traffic(cell["traffic"])
    system = serve_driver.System(cfg, 31, interpret)
    reqs = tiny_bench.traffic_kind(mix["kind"]).generate(
        mix, 31, 3.0, system.mc.vocab_size)
    batcher = system.batcher()
    serve_driver.warm(system, batcher, reqs)
    counter = serve_driver.CompileCounter()
    win = serve_driver.serve_window(batcher, reqs, 3.0,
                                    compile_counter=counter)
    assert win["ticks"] > 0 and win["finished"] > 0
    assert win["compiles_in_window"] == 0


@pytest.mark.parametrize("env", [None, "pallas_interpret", "ref"])
def test_refuses_kernels_that_are_not_compiled(tiny_bench, monkeypatch, env):
    """A run times compiled Pallas kernels only: on the CPU, or with the
    kernel mode set to anything else, the system under test is not built."""
    if env is None:
        monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    else:
        monkeypatch.setenv("REPRO_KERNEL_MODE", env)
    cfg = tiny_bench.config("tiny-ssm")
    with pytest.raises(RuntimeError, match="not 'pallas'"):
        serve_driver.System(cfg, 31)


def test_warm_refuses_a_forward_in_another_mode(tiny_bench, interpret,
                                                monkeypatch):
    """Set-up checks each served forward's resolution, not only the mode
    the process starts in."""
    from repro.kernels import dispatch

    cfg = tiny_bench.config("tiny-ssm")
    mix = tiny_bench.traffic("tiny-chat")
    system = serve_driver.System(cfg, 32, interpret)
    reqs = tiny_bench.traffic_kind(mix["kind"]).generate(
        mix, 32, 1.0, system.mc.vocab_size)
    monkeypatch.setenv("REPRO_KERNEL_MODE", dispatch.REF)
    with pytest.raises(RuntimeError, match="not all compiled"):
        serve_driver.warm(system, system.batcher(), reqs)
