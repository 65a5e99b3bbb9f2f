"""The program's own names in a trace (``harness/program.py``): op time
by named scope and kernel name inside the complete executions, the
engine's host spans, idle time by the span it falls in, the conv
roofline's least time, and the numbers read from them."""
import collections
import json

import jax
import pytest

import sd15_cost as cost
from harness import program, trace
from perfbench_fixtures import tiny_spec
from test_perfbench_readers import PEAK, mix, offline_trace, view

MS = 1_000_000_000          # picoseconds per millisecond

Q8 = ('%q8_matmul.1 = f32[308,768]{1,0} custom-call(bf16[308,768]{1,0} %a, '
      's8[768,768]{1,0} %b, f32[24,768]{1,0} %c), '
      'custom_call_target="tpu_custom_call"')
FLASH = ('%flash_attention.2 = bf16[32,4096,40]{2,1,0} custom-call('
         'bf16[32,4096,40]{2,1,0} %q, bf16[32,4096,40]{2,1,0} %k, '
         'bf16[32,4096,40]{2,1,0} %v), custom_call_target="tpu_custom_call"')
# (HLO text, tf_op or None, start ms, duration ms) of one execution of
# program 7 from 2 to 8 ms; the copy reads fusion.1, the weight's
# prefetch is read by fusion.3.
OPS = [
    ("%while.1 = (s32[]) while(s32[] %x), body=%b", None, 2.0, 5.5),
    (Q8, "jit(counted)/clip/q8_matmul/pallas_call:", 2.0, 0.5),
    ("%fusion.1 = bf16[4,64,64,320]{3,2,1,0} fusion(bf16[4,64,64,2880]"
     "{3,2,1,0} %p), kind=kOutput, calls=%fc.1",
     "jit(counted)/while/body/unet/conv/dot_general:", 2.5, 1.0),
    ("%copy.2 = bf16[4,64,64,320]{2,3,1,0} copy(bf16[4,64,64,320]"
     "{3,2,1,0} %fusion.1)", None, 3.5, 0.5),
    ("%copy-start.1 = (bf16[3,3]{1,0}, bf16[3,3]{1,0}, u32[]) copy-start("
     "bf16[3,3]{1,0} %param.1)", None, 4.0, 0.1),
    (FLASH, "jit(counted)/while/body/unet/flash_attention/pallas_call:",
     4.5, 0.5),
    ("%fusion.3 = bf16[4,512,512,128]{3,2,1,0} fusion(bf16[3,3]{1,0} "
     "%copy-start.1), kind=kOutput, calls=%fc.3",
     "jit(counted)/vae/mul:", 5.0, 1.5),
    ("%fusion.4 = f32[1]{0} fusion(f32[4]{0} %plan), kind=kLoop, "
     "calls=%fc.4", "jit(counted)/while/body/dynamic_slice:", 6.5, 0.5),
]
SMALL = ("%copy.9 = s32[77]{0} copy(s32[77]{0} %a)", None, 8.5, 0.5)
HOST = [
    ("bench.wait_arrival", 0.0, 1.0, {}),
    ("bench.step", 1.0, 1.0, {}),
    ("engine.step", 1.1, 0.8, {}),
    ("engine.pack", 1.2, 0.3, {"rows": 2}),
    ("engine.launch", 1.5, 0.3, {"rids": "1 2", "rows": 2, "bucket": 1,
                                 "steps": 1, "sampler": "euler",
                                 "cfg": 0}),
    ("bench.wait_device", 2.0, 8.0, {}),
]


def _quote(s: str) -> str:
    return json.dumps(s)


def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _pb(field: int, value) -> bytes:
    """One protobuf field: a varint, or a string/message by length."""
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _instr(name, opcode, op_name, calls=()):
    return _pb(2, (_pb(1, name) + _pb(2, opcode) + _pb(7, _pb(2, op_name))
                   + (_pb(38, b"".join(_varint(c) for c in calls))
                      if calls else b"")))


# Program 7's HLO: fusion.3 (whose own path is the activation it
# absorbed) fuses a multiply and the conv's patches.
HLO = _pb(1, _pb(3, _pb(5, 1) + _instr("fusion.3", "fusion",
                                       "jit(counted)/vae/mul", [2]))
          + _pb(3, _pb(5, 2)
                + _instr("multiply.1", "multiply", "jit(counted)/vae/mul")
                + _instr("conv.1", "convolution",
                         "jit(counted)/vae/conv/conv_general_dilated")))


def xspace(ops=OPS, host=HOST, hlo=HLO) -> bytes:
    """A serialized XSpace laid out as a TPU trace: op metadata with
    ``tf_op`` and ``program_id`` stats, ``XLA Modules``/``XLA Ops``
    lines, program 7's HLO in the metadata plane, and host spans with
    args on the python thread."""
    meta, events = [], []
    for i, (name, path, start, dur) in enumerate(list(ops) + [SMALL], 1):
        pid = 9 if name == SMALL[0] else 7
        short = name[1:name.index(" ")]
        stats = f"stats {{ metadata_id: 2 uint64_value: {pid} }}"
        if path:
            stats += f" stats {{ metadata_id: 1 str_value: {_quote(path)} }}"
        meta.append(f"event_metadata {{ key: {i} value {{ id: {i} "
                    f"name: {_quote(name)} display_name: {_quote(short)} "
                    f"{stats} }} }}")
        events.append(f"events {{ metadata_id: {i} offset_ps: "
                      f"{int(start * MS)} duration_ps: {int(dur * MS)} }}")
    meta.append('event_metadata { key: 100 value { id: 100 '
                'name: "jit_counted(7)" } }')
    meta.append('event_metadata { key: 101 value { id: 101 '
                'name: "jit_small(9)" } }')
    device = (
        'planes { id: 1 name: "/device:TPU:0" '
        'lines { id: 1 name: "XLA Modules" timestamp_ns: 0 '
        f'events {{ metadata_id: 100 offset_ps: {2 * MS} '
        f'duration_ps: {6 * MS} }} '
        f'events {{ metadata_id: 101 offset_ps: {int(8.5 * MS)} '
        f'duration_ps: {int(0.5 * MS)} }} }} '
        'lines { id: 2 name: "XLA Ops" timestamp_ns: 0 '
        + " ".join(events) + " } " + " ".join(meta) +
        ' stat_metadata { key: 1 value { id: 1 name: "tf_op" } }'
        ' stat_metadata { key: 2 value { id: 2 name: "program_id" } } }')
    names = sorted({k for *_, args in host for k in args})
    sid = {k: i for i, k in enumerate(names, 1)}
    hmeta, hev = [], []
    for i, (name, start, dur, args) in enumerate(host, 1):
        stats = " ".join(
            f"stats {{ metadata_id: {sid[k]} "
            + (f"int64_value: {v}" if isinstance(v, int)
               else f"str_value: {_quote(v)}") + " }"
            for k, v in args.items())
        hmeta.append(f"event_metadata {{ key: {i} value {{ id: {i} "
                     f"name: {_quote(name)} }} }}")
        hev.append(f"events {{ metadata_id: {i} offset_ps: "
                   f"{int(start * MS)} duration_ps: {int(dur * MS)} "
                   f"{stats} }}")
    hstat = " ".join(f"stat_metadata {{ key: {i} value {{ id: {i} "
                     f"name: {_quote(k)} }} }}" for k, i in sid.items())
    cpu = ('planes { id: 2 name: "/host:CPU" '
           'lines { id: 1 name: "python" timestamp_ns: 0 '
           + " ".join(hev) + " } " + " ".join(hmeta) + " " + hstat + " }")
    esc = "".join(f"\\{b:03o}" for b in hlo)
    meta_plane = (
        'planes { id: 3 name: "/host:metadata" event_metadata { key: 1 '
        'value { id: 1 name: "jit_counted(7)" stats { metadata_id: 1 '
        f'bytes_value: "{esc}" }} }} }} stat_metadata {{ key: 1 value {{ '
        'id: 1 name: "Hlo Proto" } } }')
    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        device + " " + cpu + (" " + meta_plane if hlo else ""))


@pytest.fixture
def xplane(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace())
    return str(path)


@pytest.mark.parametrize("path,scopes", [
    ("jit(counted)/while/body/closed_call/unet/conv/dot_general:",
     ("unet", "conv")),
    ("jit(counted)/clip/while:", ("clip",)),
    ("jit(counted)/vae/conv/conv_general_dilated:", ("vae", "conv")),
    ("jit(counted)/while/body/dynamic_slice:", ()),
    ("jit(counted)/unet_extra/convolve:", ()),
    ("", ())])
def test_scopes_of(path, scopes):
    assert program.scopes_of(path) == scopes


@pytest.mark.parametrize("name,kernel", [
    (Q8, "q8_matmul"), (FLASH, "flash_attention"),
    ('%flash_decode_paged.1 = (bf16[8]{0}, bf16[8]{0}) custom-call(s32[8]'
     '{0} %t), custom_call_target="tpu_custom_call"', "flash_decode_paged"),
    ('%custom-call.91 = f32[8]{0} custom-call(f32[8]{0} %s), '
     'custom_call_target="ConcatBitcast"', None),
    (OPS[2][0], None)])
def test_kernel_name_is_the_custom_calls_name(name, kernel):
    assert program.kernel_name(name) == kernel


def test_a_fusion_counts_under_every_scope_it_fuses():
    assert program.op_scopes(xspace())[OPS[6][0]] == ("vae", "conv")
    assert program.op_scopes(xspace(hlo=b""))[OPS[6][0]] == ("vae",)
    buf = memoryview(HLO)
    assert program.fused_paths(buf, (0, len(buf))) == {"fusion.3": [
        "jit(counted)/vae/mul", "jit(counted)/vae/conv/conv_general_dilated"]}


def test_op_scopes_read_the_metadata_and_name_added_ops():
    scopes = program.op_scopes(xspace())
    assert scopes[Q8] == ("clip",)
    assert scopes[OPS[3][0]] == ("unet", "conv")   # the copy: what it reads
    assert scopes[OPS[4][0]] == ("vae", "conv")    # the prefetch: its reader
    assert OPS[0][0] not in scopes and SMALL[0] not in scopes
    assert program.operands(OPS[6][0]) == ["copy-start.1"]


def test_read_xplane_ops_scopes_and_host_args(xplane):
    ops, modules, host = program.read_xplane(xplane)
    assert [len(ops), len(modules)] == [1, 1]
    by = {o[0]: o for o in ops[0]}
    assert by[OPS[3][0]][3] == ("unet", "conv")
    assert by[OPS[3][0]][1:3] == pytest.approx((3.5e-3, 4.0e-3))
    assert [h[0] for h in host] == [h[0] for h in HOST]
    assert host[4][3] == HOST[4][3]


def test_reduce_scope_sums_kernels_spans_and_idle(xplane):
    pr = program.reduce(xplane)
    assert pr["executions"] == 1
    assert pr["execution_s"] == pytest.approx(6e-3)
    assert pr["op_s"] == pytest.approx(4.6e-3)
    assert pr["scoped_s"] == pytest.approx(4.1e-3)
    assert pr["scope_s"] == pytest.approx(
        {"clip": 0.5e-3, "unet": 2.0e-3, "conv": 3.1e-3, "vae": 1.6e-3})
    assert pr["kernel_s"] == pytest.approx(
        {"q8_matmul": 0.5e-3, "flash_attention": 0.5e-3})
    assert pr["kernel_n"] == {"q8_matmul": 1, "flash_attention": 1}
    assert pr["unscoped_ops"] == [["fusion f32[1]", pytest.approx(0.5e-3)]]
    assert [s[0] for s in pr["spans"]] == ["engine.step", "engine.pack",
                                           "engine.launch"]
    assert pr["spans"][2][3]["rids"] == "1 2"
    # window 0-10 ms, busy 2-4.1, 4.5-7 and 8.5-9: idle 4.9 ms, each
    # piece under the innermost span open there
    assert pr["idle_s"] == pytest.approx({
        "bench.wait_arrival": 1.0e-3, "bench.step": 0.2e-3,
        "engine.step": 0.2e-3, "engine.pack": 0.3e-3,
        "engine.launch": 0.3e-3, "bench.wait_device": 2.9e-3})
    assert pr["idle_gaps"][0] == [0.0, pytest.approx(2e-3),
                                  "bench.wait_arrival"]


def test_program_reduce_counts_the_executions_trace_reduce_counts(xplane):
    """Both reductions select the same complete executions of one trace,
    and ``trace.reduce`` still returns its own keys alone."""
    tr = trace.reduce(xplane)
    assert set(tr) == {"window_s", "busy_s", "modules", "module_s",
                       "kernel_s", "kernel_n", "breakdown"}
    assert tr == trace.reduce_events(*trace.read_xplane(xplane))
    pr = program.reduce(xplane)
    assert pr["executions"] == tr["modules"] == 1
    assert tr["kernel_n"] == pr["kernel_n"] == {"q8_matmul": 1,
                                                "flash_attention": 1}


def test_a_trace_without_scopes_or_engine_spans(tmp_path):
    """The parent program: no scopes, unnamed kernels, no engine spans;
    sums are empty and nothing is read."""
    ops = [(n.replace("%q8_matmul.1", "%closed_call.1"), None, s, d)
           for n, _, s, d in OPS]
    host = [h for h in HOST if h[0].startswith("bench.")]
    path = tmp_path / "p.xplane.pb"
    path.write_bytes(xspace(ops, host, hlo=b""))
    pr = program.reduce(str(path))
    assert pr["scope_s"] == {} and pr["spans"] == []
    assert pr["kernel_n"] == {"closed_call": 1, "flash_attention": 1}
    assert pr["idle_s"]["bench.step"] == pytest.approx(1e-3)
    rv = view(mix("cfg20-offline", steps=3), offline_trace())
    assert program.readings(rv, pr) == {}


def test_readers_read_nothing_without_a_program_part():
    m = mix("cfg20-offline", steps=3)
    assert program.readings(view(m, None), None) == {}
    assert program.readings(view(m, offline_trace()), None) == {}
    assert program.readings(view(m, offline_trace()),
                            _program_part(executions=0, spans=[])) == {}


def _program_part(**over):
    pr = {"executions": 2, "execution_s": 2.0, "op_s": 1.9,
          "scope_s": {"clip": 0.1, "unet": 1.2, "vae": 0.5, "conv": 0.8},
          "kernel_s": {}, "kernel_n": {}, "unscoped_ops": [],
          "spans": [["engine.step", 0.0, 0.03, {}],
                    ["engine.launch", 0.02, 0.025, {}],
                    ["engine.step", 1.0, 1.01, {}],
                    ["engine.step", 2.0, 2.05, {}],
                    ["engine.launch", 2.04, 2.045, {}],
                    ["engine.step", 3.0, 3.02, {}],
                    ["engine.launch", 3.01, 3.011, {}]],
          "idle_s": {}, "idle_gaps": []}
    pr.update(over)
    return pr


def test_scope_and_step_readers():
    m = mix("cfg20-offline", steps=3)
    rv = view(m, offline_trace(evals=8))              # 2 executions
    got = program.readings(rv, _program_part())
    assert got["vae_device_s"] == pytest.approx(0.25)
    assert got["unet_eval_device_s"] == pytest.approx(1.2 / 2 / 8)
    # steps that launched: 30, 50 and 20 ms; the one at 1 s did not
    assert got["engine_step_host_s"] == pytest.approx(0.03)
    got = program.readings(rv, _program_part(scope_s={"clip": 0.1}))
    assert set(got) == {"engine_step_host_s"}
    # no whole count of UNet evaluations: nothing divided by it
    tr = offline_trace(evals=8)
    tr["kernel_n"]["flash_attention"] += 1
    got = program.readings(view(m, tr), _program_part())
    assert set(got) == {"vae_device_s", "engine_step_host_s"}


def test_conv_min_seconds_counts_each_input_once():
    """Weights once in the file's format, input and output once in
    bf16: a 3x3 site reads K/9 channels per row, a 1x1 site K, a
    ``down`` site four input rows per output row."""
    spec = tiny_spec("q8_0")                 # convs are f16 in the file
    pf, bw = 1e12, 1e9
    sites = [("conv", "conv", 16, 32, 288, 2),
             ("proj_in", "conv", 16, 32, 32, 1),
             ("down", "conv", 4, 32, 288, 1),
             ("self_qkv", "attn_qkv", 16, 32, 32, 1)]

    def least(m, n, k, c, x):
        f = 2.0 * m * n * k * c
        b = c * (n * k * 2 + (x + m * n) * 2)
        return max(f / pf, b / bw)

    want = (least(16, 32, 288, 2, 16 * 32) + least(16, 32, 32, 1, 16 * 32)
            + least(4, 32, 288, 1, 16 * 32))
    assert program.conv_min_seconds(sites, spec, cost, pf, bw) == \
        pytest.approx(want)


def test_conv_roofline_reader():
    m = mix("cfg20-offline", steps=3)
    spec = tiny_spec("q3_k")
    rv = view(m, offline_trace(evals=8))
    pf, bw = PEAK["bf16_flops_per_s"], PEAK["hbm_bytes_per_s"]
    least = (program.conv_min_seconds(cost.unet_sites(spec, 4), spec, cost,
                                      pf, bw) * 8
             + program.conv_min_seconds(cost.vae_sites(spec, 4), spec, cost,
                                        pf, bw))
    assert program.readings(rv, _program_part())["conv_roofline"] == \
        pytest.approx(100 * least * 2 / 0.8)


@pytest.mark.parametrize("size", ["tiny", "sd15"])
def test_conv_sites_match_the_programs_convolutions(size):
    """Every conv-role site of the cost model is one of the program's
    convolutions with the kernel area ``conv_area`` gives it: the same
    multiset of (output channels, K, kernel area) in the UNet and the
    VAE."""
    import sd15 as family
    from repro.engine import init_pipeline
    from repro.models.unet import Conv
    spec = tiny_spec("q8_0") if size == "tiny" else _sd15_spec()
    cfg = family.program_config(spec)
    params = jax.eval_shape(lambda: init_pipeline(jax.random.PRNGKey(0),
                                                  cfg))
    for part, sites in (("unet", cost.unet_sites(spec, 1)),
                        ("vae", cost.vae_sites(spec, 1))):
        convs = [x for x in jax.tree_util.tree_leaves(
            params[part], is_leaf=lambda x: isinstance(x, Conv))
            if isinstance(x, Conv)]
        got = collections.Counter(
            (c.lin.w.shape[0], c.lin.w.shape[1], c.k * c.k) for c in convs)
        want = collections.Counter(
            (n, k, program.conv_area(name, k))
            for name, role, m, n, k, c in sites if role == "conv")
        assert got == want, part


def _sd15_spec() -> dict:
    import os
    from perfbench_paths import BENCH
    with open(os.path.join(BENCH, "configs", "sd15-q8_0.json")) as f:
        return json.load(f)
