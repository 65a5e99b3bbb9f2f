"""Per-layer readers on a reduced trace whose counts follow from the
shapes: the arithmetic of padding, utilization and rooflines, and that
a reader reads nothing rather than a wrong number."""
import json
import os
import types

import pytest

import sd15_cost as cost
from harness import layers
from harness.trace import RunView
from perfbench_fixtures import BENCH, load, tiny_spec

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def view(mix, tr, win=None, policy="q3_k"):
    return RunView(win=win or types.SimpleNamespace(), trace=tr,
                   spec=tiny_spec(policy), mix=mix, cost=cost,
                   peaks={"TPU v5 lite": PEAK}, kind="TPU v5 lite")


def mix(name, **request):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        m = json.load(f)
    m["request"].update(request)
    return m


def reader(name):
    return load(os.path.join(BENCH, "metrics", name + ".py"),
                "perfbench_metric_" + name.replace(".", "_"))


def offline_trace(evals=8, modules=2):
    """Per execution of the tiny CFG program: 2 prompt encodings x 2
    CLIP layers and ``evals`` UNet evaluations x 7 transformers x 2
    attentions run flash attention, and the Q3_K kernel runs every
    layer whose K 256 divides."""
    spec = tiny_spec("q3_k")
    sites = (cost.clip_sites(spec, 4) * 2 + cost.unet_sites(spec, 4) * evals
             + cost.vae_sites(spec, 4))
    q3k = len(cost.matmul_calls(spec, sites, "q3_k")) * modules
    flash = (2 * 2 + evals * 14) * modules
    return {"modules": modules, "window_s": 10.0, "busy_s": 9.5,
            "module_s": [8.0],
            "kernel_n": {"flash_attention": flash, "q3k_matmul": q3k},
            "kernel_s": {"flash_attention": 2.0, "q3k_matmul": 1.0}}


def test_padded_eval_share_from_flash_events():
    m = mix("cfg20-offline", steps=3)          # bucket 4 per branch
    rv = view(m, offline_trace(evals=8))
    assert reader("padded_eval_share.offline").read(rv) == \
        pytest.approx(25.0)
    rv = view(m, offline_trace(evals=6))       # no padding run
    assert reader("padded_eval_share.offline").read(rv) == \
        pytest.approx(0.0)


@pytest.mark.parametrize("kernel,delta", [("flash_attention", 1),
                                          ("flash_attention", 14 * 2),
                                          ("q3k_matmul", -1)])
def test_a_count_the_shapes_do_not_give_reads_nothing(kernel, delta):
    """Flash events one off, or off by one whole UNet evaluation per
    execution while the matmul kernel's events say otherwise."""
    m = mix("cfg20-offline", steps=3)
    tr = offline_trace()
    tr["kernel_n"][kernel] += delta
    rv = view(m, tr)
    assert reader("padded_eval_share.offline").read(rv) is None
    assert reader("flash_attention_roofline.offline").read(rv) is None
    assert reader("q3k_matmul_roofline.offline").read(rv) is None
    assert reader("device_idle_share.offline").read(view(m, None)) is None


def test_flash_roofline_is_least_time_over_event_time():
    m = mix("cfg20-offline", steps=3)
    spec = tiny_spec("q3_k")
    rv = view(m, offline_trace())
    sites = (cost.clip_sites(spec, 4) * 2 + cost.unet_sites(spec, 4) * 8
             + cost.vae_sites(spec, 4))
    least = cost.attention_min_seconds(cost.attention_calls(sites),
                                       PEAK["bf16_flops_per_s"],
                                       PEAK["hbm_bytes_per_s"])[0]
    assert reader("flash_attention_roofline.offline").read(rv) == \
        pytest.approx(100 * least * 2 / 2.0)


def test_matmul_roofline_counts_the_kernel_calls():
    m = mix("turbo-poisson")
    spec = tiny_spec("q8_0")
    sites = (cost.clip_sites(spec, 4) + cost.unet_sites(spec, 4)
             + cost.vae_sites(spec, 4))
    calls = cost.matmul_calls(spec, sites, "q8_0")
    tr = {"modules": 3, "window_s": 5.0, "busy_s": 4.0, "module_s": [3.0],
          "kernel_n": {"flash_attention": 3 * (2 + 14),
                       "q8_matmul": 3 * len(calls)},
          "kernel_s": {"flash_attention": 0.5, "q8_matmul": 0.25}}
    rv = view(m, tr, policy="q8_0")
    least = cost.matmul_min_seconds(calls, "q8_0", PEAK["bf16_flops_per_s"],
                                    PEAK["hbm_bytes_per_s"])[0]
    assert reader("q8_matmul_roofline.serve").read(rv) == pytest.approx(
        100 * least * 3 / 0.25)
    tr["kernel_n"]["q8_matmul"] -= 1
    assert reader("q8_matmul_roofline.serve").read(rv) is None


def test_mfu_and_idle_share():
    m = mix("turbo-poisson")
    spec = tiny_spec("q8_0")
    rec = {i: {"due": 0.0, "dispatch": 1.0, "ready": 2.0} for i in range(5)}
    win = types.SimpleNamespace(
        rec=rec, trace_t0=0.5, t_close=3.0,
        batches=[{"dispatch": 1.0, "rows": 3, "rids": [0, 1, 2]},
                 {"dispatch": 1.5, "rows": 2, "rids": [3, 4]}])
    tr = {"modules": 2, "window_s": 5.0, "busy_s": 4.0, "module_s": [2.0],
          "kernel_n": {}, "kernel_s": {}}
    rv = view(m, tr, win, policy="q8_0")
    per_req = cost.request_flops(spec, 1, False)
    assert reader("mfu.serve").read(rv) == pytest.approx(
        100 * 5 * per_req / (2.0 * 197e12))
    assert reader("device_idle_share.serve").read(rv) == pytest.approx(20.0)
    assert reader("batch_fill.serve").read(rv) == pytest.approx(62.5)
    tr["modules"] = 3                    # the host saw one ready late
    assert reader("mfu.serve").read(rv) == pytest.approx(
        100 * 7.5 * per_req / (2.0 * 197e12))
    win.trace_t0 = 5.0                   # no batch after the trace began
    assert reader("mfu.serve").read(rv) is None
    assert layers.p95([]) is None
