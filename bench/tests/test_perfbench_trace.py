"""Trace reduction: busy union, idle share, kernel sums, idle gaps, and
how device ops are told apart."""
import os

import pytest

from harness import trace

Q8 = ('%closed_call.600 = f32[308,768]{1,0:T(8,128)S(1)} custom-call('
      'bf16[308,768]{1,0} %reshape.5613, s8[768,768]{1,0} %fusion.13, '
      'f32[24,768]{1,0} %fusion.180), custom_call_target="tpu_custom_call", '
      'operand_layout_constraints={bf16[308,768]{1,0}}')
Q3K = ('%closed_call.7 = f32[308,1280]{1,0} custom-call(bf16[308,1280]{1,0} '
       '%a, u8[1280,320]{1,0} %b, u8[1280,160]{1,0} %c, u8[80,1280]{1,0} %d, '
       'f32[5,1280]{1,0} %e), custom_call_target="tpu_custom_call"')
FLASH = ('%closed_call.566 = bf16[32,4096,40]{2,1,0:T(8,128)(2,1)} '
         'custom-call(bf16[32,4096,40]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.1, '
         'bf16[32,4096,40]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.3413, '
         'bf16[32,4096,40]{2,1,0:T(8,128)(2,1)} %bitcast.3422), '
         'custom_call_target="tpu_custom_call", operand_layout={x}')
CONCAT = ('%custom-call.91 = f32[1280,1280]{1,0} custom-call('
          'f32[320,1280]{1,0} %slice-done.236), '
          'custom_call_target="ConcatBitcast"')
FUSION = ('%convolution_convert_fusion = f16[4,512,512,256,9]{3,0,4,2,1} '
          'fusion(bf16[3,3,1,256,9]{3,2,4,1,0} %copy-done.116), kind=kOutput')


@pytest.mark.parametrize("name,kernel", [
    (Q8, "q8_matmul"), (Q3K, "q3k_matmul"), (FLASH, "flash_attention"),
    (CONCAT, None), (FUSION, None)])
def test_kernel_of(name, kernel):
    assert trace.kernel_of(name) == kernel


def test_union_length_and_merged():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert trace.union_length(iv) == pytest.approx(3.0)
    assert trace.merged(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert trace.union_length([]) == 0.0


def small_trace():
    """One device, a 10 s traced window: two whole executions of the
    served program (with kernels), one cut by the window's end, one
    small eager program; host spans tile the window."""
    ops = [
        ("%copy.1 = s32[77] copy(s32[77] %a)", 0.5, 1.0),
        (Q8, 1.0, 2.0), (FLASH, 2.0, 2.5), (Q8, 2.5, 3.0),
        (FUSION, 3.0, 3.5),
        (Q8, 5.0, 6.0), (FLASH, 6.0, 6.5), (Q8, 6.5, 7.0),
        (FLASH, 9.0, 11.0),
    ]
    modules = [("jit_small(1)", 0.5, 1.0), ("jit_counted(2)", 1.0, 3.5),
               ("jit_counted(2)", 5.0, 7.0), ("jit_counted(2)", 9.0, 11.0)]
    host = [("bench.wait_arrival", 0.0, 1.0), ("bench.wait_device", 1.0, 3.5),
            ("bench.step", 3.5, 5.0), ("bench.wait_device", 5.0, 7.0),
            ("bench.wait_arrival", 7.0, 9.0), ("bench.wait_device", 9.0, 10.0)]
    return [ops], [modules], host


def test_reduce_events_busy_idle_kernels():
    tr = trace.reduce_events(*small_trace())
    assert tr["window_s"] == pytest.approx(10.0)
    # busy: [0.5, 3.5] + [5, 7] + [9, 10] (clipped at the window's end)
    assert tr["busy_s"] == pytest.approx(6.0)
    assert tr["modules"] == 2                 # the cut one does not count
    assert tr["module_s"] == [pytest.approx(4.5)]
    assert tr["kernel_n"] == {"q8_matmul": 4, "flash_attention": 2}
    assert tr["kernel_s"]["q8_matmul"] == pytest.approx(3.0)
    assert tr["kernel_s"]["flash_attention"] == pytest.approx(1.0)
    gaps = tr["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.wait_arrival", pytest.approx(2.0)]
    assert ["bench.step", pytest.approx(1.5)] in gaps
    assert ["bench.wait_arrival", pytest.approx(0.5)] in gaps
    ops = dict((n, s) for n, s in tr["breakdown"]["device_ops"])
    assert ops["q8_matmul"] == pytest.approx(3.0)
    assert ops["flash_attention"] == pytest.approx(2.0)  # 1 whole, 1 cut
    assert ops["convolution_convert_fusion f16[4,512,512,256,9]"] == \
        pytest.approx(0.5)


def test_an_execution_missing_events_is_left_out():
    ops, modules, host = small_trace()
    ops = [[o for o in ops[0] if o[1] != 1.0]]   # lose the first Q8 event
    tr = trace.reduce_events(ops, modules, host)
    assert tr["modules"] == 1
    assert tr["module_s"] == [pytest.approx(2.0)]
    assert tr["kernel_n"] == {"q8_matmul": 2, "flash_attention": 1}


def test_control_flow_ops_count_through_their_body():
    ops, modules, host = small_trace()
    loop = "%while.5 = (s32[]{:T(128)}, f32[4,64,64,4]) while(...)"
    ops = [ops[0] + [(loop, 1.0, 7.0)]]
    tr = trace.reduce_events(ops, modules, host)
    assert tr["busy_s"] == pytest.approx(6.0)
    assert all(not n.startswith("while") for n, _ in
               tr["breakdown"]["device_ops"])


def test_reduce_events_without_device_or_host():
    ops, modules, host = small_trace()
    assert trace.reduce_events([], [], host) is None
    assert trace.reduce_events(ops, modules, []) is None


def test_host_spans_are_read_from_a_recorded_trace(tmp_path):
    """The loop's spans land on the profiler's clock in a real trace."""
    import jax
    import jax.numpy as jnp
    from harness.serve import Spans
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    spans = Spans()
    spans.on = True
    spans.enter("bench.step")
    y = f(x)
    spans.enter("bench.wait_device")
    y.block_until_ready()
    spans.close()
    jax.profiler.stop_trace()
    paths = [os.path.join(d, n) for d, _, fs in os.walk(tmp_path)
             for n in fs if n.endswith(".xplane.pb")]
    ops, modules, host = trace.read_xplane(paths[0])
    names = [h[0] for h in host]
    assert names == ["bench.step", "bench.wait_device"]
    assert host[0][2] <= host[1][1] + 1e-3
    assert ops == []                         # no TPU plane on this host
