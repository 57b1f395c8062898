"""The reduction from a profiler trace to busy time, idle gaps, kernel
time and exposed collectives, on a small recorded trace."""
import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce as tr

# device 0: two decode kernels overlapping a fusion, a gap, an all-reduce
# overlapping a matmul; times in ns
OPS = [("fusion.1", 0, 100), ("paged_attention_decode.2", 50, 150),
       ("paged_attention_decode.7", 160, 200), ("all-reduce.3", 400, 500),
       ("convolution.4", 450, 600)]
HOST = [("engine step", 0, 1000), ("collect", 210, 390),
        (tr.OPEN, 20, 20), (tr.CLOSE, 580, 580)]


def test_union_and_busy():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    # [0,150] + [160,200] + [400,600]
    assert tr.busy_ns(OPS) == 150 + 40 + 200


def test_idle_share_and_summary():
    trace = tr.window({"devices": {0: OPS, 1: OPS[:1]}, "host": HOST})
    # device ops cut to the marks' span [20, 580]
    assert trace["devices"][1] == [("fusion.1", 20, 100)]
    s = tr.summarize(trace, chips=2)
    assert s["busy_s"] == pytest.approx((130 + 40 + 180 + 80) / 2 * 1e-9)
    assert s["window_s"] == pytest.approx(560e-9)
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["paged_attention_decode"] == pytest.approx(140e-9)
    # the longest gap on device 0 is [200, 400], inside the host's collect
    label, secs = s["breakdown"]["idle_gaps"][0]
    assert (label, secs) == ("collect", pytest.approx(200e-9))


def test_op_name_of_an_hlo_instruction():
    text = "%paged_attention_decode.5 = (bf16[64,896]) custom-call(s32[64] %p)"
    assert tr.op_name(text) == "paged_attention_decode.5"
    assert tr.op_name("fusion.3") == "fusion.3"


def test_kernel_sums():
    assert tr.kernel_ns(OPS, r"^paged_attention_decode\b") == (140.0, 2)
    assert tr.kernel_ns(OPS, r"^all-reduce") == (100.0, 1)


def test_read_xplane_of_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("decode_step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    got = tr.read_xplane(str(tmp_path))
    assert got["devices"] == {}          # the CPU backend has no TPU plane
    assert any(name == "decode_step" for name, _, _ in got["host"])


def test_window_needs_its_marks():
    with pytest.raises(ValueError, match="window marks"):
        tr.window({"devices": {0: OPS}, "host": HOST[:2]})
