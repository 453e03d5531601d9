"""trace.py reduces a small trace recorded here to busy and idle time, per
op time, and idle gaps named by the harness's host spans.  On the CPU the
XLA client's threads stand in for a device's op line."""
from __future__ import annotations

import re
import time

from chip_bench_tiny import BENCH  # noqa: F401  (puts the harness on the path)

import trace as trace_mod

CPU_OPS = dict(device_plane=re.compile(r"^/host:CPU$"),
               op_line="tf_XLAPjRtCpuClient")


def test_reduce_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    t0 = time.monotonic()
    with trace_mod.record(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(4):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.submit"):
                    time.sleep(0.02)
    window = time.monotonic() - t0
    red = trace_mod.reduce(trace_mod.xplane_file(str(tmp_path)), chips=1,
                           **CPU_OPS)
    assert red.devices == 1
    assert 0 < red.busy_s < window
    assert any(name.startswith("dot") for name in red.ops)
    assert sum(red.ops.values()) >= red.busy_s * 0.999
    # the sleeps leave the device idle, inside the submit spans
    assert red.idle_gaps.get("bench.submit", 0.0) >= 3 * 0.02 * 0.9
    assert set(red.idle_gaps) <= {"bench.submit", "bench.step",
                                  "bench.window", "untraced"}
    assert red.span_s["bench.step"] > 0
    bd = trace_mod.breakdown(red)
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert len(bd["device_ops"]) <= 10
    assert bd["idle_gaps"][0][0] == "bench.submit"


def test_union_merges_overlaps():
    assert trace_mod._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3],
                                                                [5, 8]]


def test_op_name_keeps_name_and_operand_shapes():
    hlo = ("%scatter_or.1 = s32[2056,128]{1,0:T(8,128)S(1)} custom-call("
           "s32[2056,128]{1,0:T(8,128)S(1)} %pad, s32[1024,128]{1,0} %f), "
           "custom_call_target=\"tpu_custom_call\", "
           "frontend_attributes={kernel_metadata={}}")
    assert trace_mod._op_name(hlo) == ("%scatter_or.1 = s32[2056,128] "
                                       "custom-call(s32[2056,128] %pad, "
                                       "s32[1024,128] %f)")
    tup = ("%r = (s32[32]{0:T(128)}, s32[8,32]{0,1}) fusion(u32[8]{0} %x), "
           "kind=kLoop, calls=%c")
    assert trace_mod._op_name(tup) == "%r = (s32[32], s32[8,32]) fusion(u32[8] %x)"
    assert trace_mod._op_name("dot.3") == "dot.3"
