"""The reduction from a profiler trace to busy time, kernel time and the
breakdown: on hand-made events, and on a profile recorded here on the CPU
(whose XLA thread lines stand in for a device's operation line)."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench.harness import trace as tr


def ev(name, s, t):
    return tr.Event(name, float(s), float(t))


def test_busy_is_the_union_of_overlapping_and_clipped_intervals():
    evs = [ev("a", 0, 10), ev("b", 5, 15), ev("c", 20, 30), ev("d", 28, 29),
           ev("e", 90, 120)]
    assert tr.busy_intervals(evs, 2, 100) == [(2, 15), (20, 30), (90, 100)]
    prof = tr.Profile(devices={"/device:TPU:0": evs}, host=[])
    assert tr.busy_seconds(prof, 2, 100) == pytest.approx((13 + 10 + 10) / 1e9)


def test_busy_and_kernel_time_average_over_devices():
    prof = tr.Profile(devices={
        "/device:TPU:0": [ev("kernel.1", 0, 40), ev("fusion.2", 40, 50)],
        "/device:TPU:1": [ev("kernel.7", 0, 20)]}, host=[])
    assert tr.busy_seconds(prof, 0, 100) == pytest.approx(35 / 1e9)
    secs = tr.op_seconds(prof, 0, 100, lambda n: n.startswith("kernel"))
    assert secs == pytest.approx(30 / 1e9)
    assert tr.top_ops(prof, 0, 100) == [["kernel", pytest.approx(30 / 1e9)],
                                        ["fusion", pytest.approx(5 / 1e9)]]


def test_idle_gaps_are_longest_first_and_named_by_host_activity():
    dev = [ev("op", 0, 10), ev("op", 40, 50), ev("op", 55, 100)]
    host = [ev("bench.window", 0, 100), ev("step", 5, 45),
            ev("sample", 12, 38), ev("admit", 50, 56)]
    prof = tr.Profile(devices={"/device:TPU:0": dev}, host=host)
    gaps = tr.idle_gaps(prof, 0, 100)
    assert [g[0] for g in gaps] == ["sample", "admit"]
    assert gaps[0][1] == pytest.approx(30 / 1e9)
    assert gaps[1][1] == pytest.approx(5 / 1e9)


def test_reduction_of_a_recorded_profile(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        t0 = time.perf_counter()
        for _ in range(5):
            f(x).block_until_ready()
        wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    prof = tr.read_profile(tr.find_profile(str(tmp_path)),
                           device_prefix="/host:CPU",
                           device_lines=("tf_XLAPjRtCpuClient",))
    lo, hi = prof.window()
    assert 0 < (hi - lo) / 1e9 < wall + 0.5
    s = tr.summarize(prof, {"dot": lambda n: n.startswith("dot")})
    assert 0 < s["ops_s"]["dot"] <= s["busy_s"] <= s["window_s"]
    names = [n for n, _ in s["breakdown"]["device_ops"]]
    assert "dot_general" in names
    assert s["breakdown"]["idle_gaps"]


def test_labels_and_self_time_of_nested_operations():
    assert tr.op_label("%decode_attention_paged.1 = (f32[8]) custom-call(")\
        == "decode_attention_paged"
    assert tr.op_label("%fusion.12.3 = bf16[2] fusion(") == "fusion"
    assert tr.op_label("copy-start") == "copy-start"
    # a loop's event covers its body's events: each counts its own time
    evs = [ev("%while.3 = (...) while(", 0, 100), ev("%fusion.1 = f(", 10, 30),
           ev("%while.4 = (...) while(", 40, 90), ev("%dot.2 = d(", 50, 60)]
    own = tr.self_seconds(evs, 0, 100)
    assert own["while"] == pytest.approx((100 - 20 - 50 + 50 - 10) / 1e9)
    assert own["fusion"] == pytest.approx(20 / 1e9)
    assert own["dot"] == pytest.approx(10 / 1e9)
    assert sum(own.values()) == pytest.approx(100 / 1e9)
