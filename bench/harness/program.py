"""Reduction of a profiler trace to the program's own parts.

Where ``trace.py`` tells device ops apart by their HLO text, this module
reads the names the program gives its parts:

* device op time by named-scope component (``clip``, ``unet``, ``vae``,
  ``conv``: an op under ``unet/conv`` counts in both) and by Pallas
  kernel name (a ``tpu_custom_call`` is named after its kernel), inside
  the complete denoise executions that ``trace.py`` selects (same rule,
  same helpers);
* the engine's ``engine.*`` host spans in the traced window, with their
  args;
* the device's idle time in the window put down to the innermost
  ``engine.*`` span that covers it, else the ``bench.*`` span, else
  ``untraced``.

An op's scopes are read once per op (``op_scopes``), from its
``op_name`` path (``jit(counted)/while/body/unet/conv/...``) and the
paths of what it fuses.  A trace of a program without scopes or named
kernels reads empty sums, and ``readings`` leaves its numbers out.

``run.py`` does not call this module yet: ``trace.reduce`` returns its
own keys alone, and the trace file is deleted once it returns, so the
numbers ``readings`` defines are not per-layer metrics of the benchmark.
"""
from __future__ import annotations

import bisect
import re
import statistics

from harness import trace

SCOPES = ("clip", "unet", "vae", "conv")
MODELS = ("clip", "unet", "vae")
SCOPE_STAT = "tf_op"
ENGINE_PREFIX = "engine."
# A Pallas kernel's custom call is named after the kernel:
# ``%q8_matmul.3 = f32[...] custom-call(...), custom_call_target=
# "tpu_custom_call"``.
INSTRUCTION = re.compile(r"^%([A-Za-z_][\w-]*?)(?:\.\d+)? = ")
TPU_CALL = 'custom_call_target="tpu_custom_call"'


def scopes_of(path: str) -> tuple[str, ...]:
    """The named scopes of an op-name path
    (``jit(counted)/while/body/unet/conv/dot_general:``), in ``SCOPES``
    order."""
    parts = set(path.rstrip(":").split("/"))
    return tuple(s for s in SCOPES if s in parts)


def kernel_name(name: str) -> str | None:
    """A ``tpu_custom_call``'s instruction name without its number."""
    if TPU_CALL not in name:
        return None
    m = INSTRUCTION.match(name)
    return m.group(1) if m else None


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """``(field, value)`` of a protobuf message in ``buf[i:end]``: an
    int for a varint, ``(start, end)`` for a length-delimited field."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")
        yield key >> 3, v


def _text(buf, v) -> str:
    return bytes(buf[v[0]:v[1]]).decode("utf-8", "replace")


def _ints(buf, v) -> list[int]:
    """A repeated integer field's value: one varint, or packed."""
    if isinstance(v, int):
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


def _entry_value(buf, entry):
    """The value (field 2) of a protobuf map entry."""
    for f, v in _fields(buf, *entry):
        if f == 2:
            return v
    return None


def _plane(buf, v):
    """``(name, event metadata, {stat id: name})`` of an XPlane (name 2,
    event_metadata 4 and stat_metadata 5 as maps; lines 3 skipped)."""
    name, metas, names = "", [], {}
    for f, v2 in _fields(buf, *v):
        if f == 2:
            name = _text(buf, v2)
        elif f == 4:
            metas.append(_entry_value(buf, v2))
        elif f == 5:
            sid, sname = 0, ""
            for f3, v3 in _fields(buf, *_entry_value(buf, v2)):
                if f3 == 1:
                    sid = v3
                elif f3 == 2:
                    sname = _text(buf, v3)
            names[sid] = sname
    return name, metas, names


def _event_meta(buf, v, names: dict) -> tuple[str, str, dict]:
    """``(name, display name, {stat name: value})`` of an
    XEventMetadata (name 2, display name 4, stats 5).  An XStat holds
    its metadata id (1) and an int (3, 4), a string (5), bytes (6, as a
    ``(start, end)`` range) or a reference (7) to the stat metadata
    whose name is the string."""
    ev = short = ""
    stats = {}
    for f, v2 in _fields(buf, *v):
        if f == 2:
            ev = _text(buf, v2)
        elif f == 4:
            short = _text(buf, v2)
        elif f == 5:
            sid = val = None
            for f3, v3 in _fields(buf, *v2):
                if f3 == 1:
                    sid = v3
                elif f3 in (3, 4, 6):
                    val = v3
                elif f3 == 5:
                    val = _text(buf, v3)
                elif f3 == 7:
                    val = names.get(v3, "")
            stats[names.get(sid, "")] = val
    return ev, short, stats


def fused_paths(buf, v) -> dict[str, list[str]]:
    """``{fusion instruction: op-name paths of what it fuses}`` of a
    serialized HloProto (module 1; HloModuleProto computations 3;
    HloComputationProto instructions 2, id 5; HloInstructionProto name
    1, opcode 2, metadata 7 with op_name 2, called computation ids
    38)."""
    comps: dict[int, list] = {}
    for f, mod in _fields(buf, *v):
        if f != 1:
            continue
        for f2, comp in _fields(buf, *mod):
            if f2 != 3:
                continue
            cid, instrs = 0, []
            for f3, v3 in _fields(buf, *comp):
                if f3 == 5:
                    cid = v3
                elif f3 == 2:
                    name = opcode = path = ""
                    calls: list[int] = []
                    for f4, v4 in _fields(buf, *v3):
                        if f4 == 1:
                            name = _text(buf, v4)
                        elif f4 == 2:
                            opcode = _text(buf, v4)
                        elif f4 == 7:
                            for f5, v5 in _fields(buf, *v4):
                                if f5 == 2:
                                    path = _text(buf, v5)
                        elif f4 == 38:
                            calls += _ints(buf, v4)
                    instrs.append((name, opcode, path, calls))
            comps[cid] = instrs

    def inside(cid: int, seen: set) -> list[str]:
        out = []
        for _, opcode, path, calls in comps.get(cid, ()):
            if path:
                out.append(path)
            if opcode == "fusion":
                for c in calls:
                    if c not in seen:
                        seen.add(c)
                        out += inside(c, seen)
        return out

    return {name: inside(calls[0], set(calls))
            for instrs in comps.values()
            for name, opcode, _, calls in instrs
            if opcode == "fusion" and calls}


OPERAND = re.compile(r"%([\w.-]+)")


def operands(name: str) -> list[str]:
    """The instruction names an op's HLO text reads (before any
    ``calls=``/attributes)."""
    head = name.split(" = ", 1)[-1].split("), ", 1)[0]
    return OPERAND.findall(head)


def op_scopes(data: bytes, chips: int = 1) -> dict[str, tuple[str, ...]]:
    """``{op event name: its named scopes}`` of the first ``chips`` TPU
    planes of a serialized XSpace.

    ``ProfileData`` gives an event its own stats but not its metadata's,
    where the op's ``tf_op`` (its ``op_name`` path) lives, nor the
    programs' HLO, so this reads both from the protobuf (XSpace planes,
    field 1) and skips the events.  An op's scopes are those of its own
    path and, for a fusion, of every instruction it fuses (a fusion
    takes one instruction's path: an im2col patch fusion that absorbed
    the activation before it would otherwise leave ``conv``); the HLO
    is the ``Hlo Proto`` stat of the ``/host:metadata`` plane's
    ``<module>(<program id>)`` entries.  An op the compiler adds (a
    relayout copy, an async copy's halves, a padding) has no path: it
    takes the scopes of the first op of its program whose output it
    reads, else of the first op that reads its output, so a copy of an
    im2col buffer counts under the ``conv`` that made it and a weight's
    prefetch under the op that uses it."""
    buf = memoryview(data)
    planes = [_plane(buf, v) for f, v in _fields(buf, 0, len(buf))
              if f == 1]
    fused: dict[int, dict] = {}
    for name, metas, names in planes:
        if name != "/host:metadata":
            continue
        for v in metas:
            mod, _, stats = _event_meta(buf, v, names)
            hlo = stats.get("Hlo Proto")
            pid = mod[mod.rfind("(") + 1:-1]
            if isinstance(hlo, tuple) and mod.endswith(")") and pid.isdigit():
                fused[int(pid)] = fused_paths(buf, hlo)
    out: dict[str, tuple] = {}
    tpu = sorted((p for p in planes if p[0].startswith("/device:TPU:")),
                 key=lambda p: p[0])[:chips]
    for _, metas, names in tpu:
        found, left, readers = {}, [], {}
        for v in metas:
            ev, short, stats = _event_meta(buf, v, names)
            if not ev:
                continue
            pid = stats.get("program_id") or 0
            for o in operands(ev):
                readers.setdefault((pid, o), []).append(short)
            paths = [p for p in [stats.get(SCOPE_STAT)]
                     + fused.get(pid, {}).get(short, []) if p]
            if paths:
                sc = set()
                for p in paths:
                    sc.update(scopes_of(str(p)))
                out[ev] = found[(pid, short)] = tuple(
                    s for s in SCOPES if s in sc)
            else:
                left.append((ev, short, pid))
        for _ in range(4):           # copy-start -> copy-done -> copy
            still = []
            for ev, short, pid in left:
                src = next((found[(pid, o)] for o in operands(ev)
                            if (pid, o) in found), None)
                if src is None:
                    src = next((found[(pid, r)] for r in
                                readers.get((pid, short), ())
                                if (pid, r) in found), None)
                if src is None:
                    still.append((ev, short, pid))
                else:
                    out[ev] = found[(pid, short)] = src
            if len(still) == len(left):
                break
            left = still
    return out


def read_xplane(path: str, chips: int = 1):
    """``(ops, modules, host)`` in seconds from an ``.xplane.pb``:
    ``ops`` per device ``(name, start, end, scopes)``, ``modules`` as in
    ``trace.read_xplane``, ``host`` the ``bench.*`` and ``engine.*``
    spans as ``(name, start, end, args)``."""
    import jax
    with open(path, "rb") as f:
        data = f.read()
    scopes = op_scopes(data, chips)
    pd = jax.profiler.ProfileData.from_serialized_xspace(data)
    del data
    ops, modules, host = [], [], []
    devices = sorted((p for p in pd.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)[:chips]
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops.append([(e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9,
                     scopes.get(e.name, ()))
                    for e in lines[trace.OPS_LINE].events])
        modules.append([(e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in lines[trace.MODULES_LINE].events])
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                name = e.name
                if (name.startswith(trace.HOST_PREFIX)
                        or name.startswith(ENGINE_PREFIX)):
                    host.append((name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9,
                                 dict(e.stats)))
    return ops, modules, host


def complete_executions(dev_ops, dev_mods, w0: float, w1: float):
    """The executions ``trace.reduce_events`` counts: wholly in the
    window, running Pallas kernels, with as many kernel events as the
    fullest such execution; sorted ``(start, end)``."""
    mods = sorted((s, e) for _, s, e in dev_mods if s >= w0 and e <= w1)
    starts = [s for s, _ in mods]
    count: dict[tuple, int] = {}
    for op in dev_ops:
        if trace.kernel_of(op[0]) is None:
            continue
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[2] <= mods[i][1]:
            count[mods[i]] = count.get(mods[i], 0) + 1
    most = max(count.values(), default=0)
    return [m for m in mods if most and count.get(m) == most]


def label_timeline(host, w0: float, w1: float):
    """Sorted ``(start, end, label)`` pieces of the window: the innermost
    ``engine.*`` span open there, else the ``bench.*`` span, else
    ``untraced``."""
    cuts = sorted({w0, w1} | {t for _, s, e, _ in host for t in (s, e)
                              if w0 < t < w1})
    spans = sorted(host, key=lambda h: h[1])
    out, active, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][1] <= a:
            active.append(spans[j])
            j += 1
        active = [h for h in active if h[2] > a]
        eng = [h for h in active if h[0].startswith(ENGINE_PREFIX)]
        pick = eng or active
        label = max(pick, key=lambda h: h[1])[0] if pick else "untraced"
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def attribute(gaps, pieces) -> list[dict]:
    """Each gap's seconds by label: ``gaps`` and ``pieces`` sorted and
    each free of overlaps."""
    out, j = [], 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        by: dict[str, float] = {}
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            o = min(ge, pieces[k][1]) - max(gs, pieces[k][0])
            if o > 0:
                by[pieces[k][2]] = by.get(pieces[k][2], 0.0) + o
            k += 1
        out.append(by)
    return out


def reduce_events(ops, modules, host, top: int = 10) -> dict | None:
    """``ops`` per device ``(name, start, end, scopes)``, ``modules`` per
    device ``(name, start, end)``, ``host`` ``(name, start, end, args)``,
    all on one clock.  ``None`` without ``bench.*`` spans or devices."""
    bench = [h for h in host if h[0].startswith(trace.HOST_PREFIX)]
    if not bench or not ops:
        return None
    w0 = min(h[1] for h in bench)
    w1 = max(h[2] for h in bench)
    scope_s: dict[str, float] = {}
    kernel_s: dict[str, float] = {}
    kernel_n: dict[str, int] = {}
    unscoped: dict[str, float] = {}
    n_exec = 0
    exec_s = op_s = scoped_s = 0.0
    pieces = label_timeline(host, w0, w1)
    idle_s: dict[str, float] = {}
    long_gaps = []
    for dev_ops, dev_mods in zip(ops, modules):
        execs = complete_executions(dev_ops, dev_mods, w0, w1)
        n_exec += len(execs)
        exec_s += sum(e - s for s, e in execs)
        starts = [s for s, _ in execs]
        for name, s, e, sc in dev_ops:
            if trace.CONTAINER.match(name):
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or e > execs[i][1]:
                continue
            d = e - s
            op_s += d
            for x in sc:
                scope_s[x] = scope_s.get(x, 0.0) + d
            if any(x in MODELS for x in sc):
                scoped_s += d
            else:
                kind = trace.op_kind(name)
                unscoped[kind] = unscoped.get(kind, 0.0) + d
            k = kernel_name(name)
            if k is not None:
                kernel_s[k] = kernel_s.get(k, 0.0) + d
                kernel_n[k] = kernel_n.get(k, 0) + 1
        iv = [(max(s, w0), min(e, w1)) for name, s, e, _ in dev_ops
              if e > w0 and s < w1 and not trace.CONTAINER.match(name)]
        gaps, prev = [], w0
        for s, e in trace.merged(iv):
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if w1 > prev:
            gaps.append((prev, w1))
        for (gs, ge), by in zip(gaps, attribute(gaps, pieces)):
            for label, t in by.items():
                idle_s[label] = idle_s.get(label, 0.0) + t
            long_gaps.append((ge - gs, gs - w0, by))
    long_gaps.sort(key=lambda g: -g[0])
    return {
        "executions": n_exec,
        "execution_s": exec_s,
        "op_s": op_s,
        "scoped_s": scoped_s,
        "scope_s": scope_s,
        "kernel_s": kernel_s,
        "kernel_n": kernel_n,
        "unscoped_ops": [[k, s] for k, s in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:top]],
        "spans": [[n, s - w0, e - w0, a] for n, s, e, a in sorted(
            host, key=lambda h: h[1])
            if n.startswith(ENGINE_PREFIX) and s >= w0 and e <= w1],
        "idle_s": idle_s,
        "idle_gaps": [[at, length, max(by, key=by.get) if by else
                       "untraced"] for length, at, by in long_gaps[:top]],
    }


def reduce(path: str, chips: int = 1) -> dict | None:
    return reduce_events(*read_xplane(path, chips))


# ------------------------------------------------------------ readings

def readings(run, pr: dict | None) -> dict[str, float]:
    """The per-layer numbers this reduction defines, from ``pr``
    (``reduce``'s result for the run's trace) and the run's view
    (``trace.RunView``); a number is left out where the trace holds
    nothing to read it from:

    * ``vae_device_s``: device time under ``vae`` per complete
      execution;
    * ``unet_eval_device_s``: device time under ``unet`` per execution
      over the UNet evaluations one execution runs (counted from its
      flash-attention events, ``layers.per_module``);
    * ``conv_roofline``: ``conv_roofline``, in %;
    * ``engine_step_host_s``: the median duration of the ``engine.step``
      spans that hold an ``engine.launch``.
    """
    from harness import layers
    out = {}
    if pr and pr["executions"]:
        scope = {k: v / pr["executions"] for k, v in pr["scope_s"].items()
                 if v}
        pm = layers.per_module(run)
        if "vae" in scope:
            out["vae_device_s"] = scope["vae"]
        if "unet" in scope and pm is not None:
            out["unet_eval_device_s"] = scope["unet"] / pm["unet_evals"]
        if "conv" in scope and pm is not None:
            out["conv_roofline"] = conv_roofline(run, pm, scope["conv"])
    steps = launching_steps(pr["spans"] if pr else [])
    if steps:
        out["engine_step_host_s"] = statistics.median(steps)
    return out


def launching_steps(spans) -> list[float]:
    """Durations of the ``engine.step`` spans (``reduce``'s ``spans``)
    that hold an ``engine.launch``."""
    launches = sorted(s for n, s, _, _ in spans if n == "engine.launch")
    out = []
    for n, s, e, _ in spans:
        if n != "engine.step":
            continue
        i = bisect.bisect_left(launches, s)
        if i < len(launches) and launches[i] < e:
            out.append(e - s)
    return out


def conv_area(name: str, k: int) -> int:
    """Kernel area of a conv-role site of ``sd15_cost``: the
    transformers' ``proj_in``/``proj_out`` and the residual blocks'
    skips are 1x1 (their K, the input channels, is no multiple of 9 in
    this family), every other convolution 3x3."""
    return 1 if name in ("proj_in", "proj_out") or k % 9 else 9


def conv_min_seconds(sites, spec: dict, cost, peak_flops: float,
                     peak_bw: float) -> float:
    """Least time of the conv-role sites at the chip's peaks, summed
    per site: the im2col product's operations against its weights once
    in the model file's format and its input and output activations
    once in bf16 (the input as the tensor the convolution reads, not as
    k x k patches; a ``down`` site reads a 2x larger side)."""
    wbytes = cost.BYTES_PER_WEIGHT[cost.fmt_of(spec, "conv")]
    tot = 0.0
    for name, role, m, n, k, c in sites:
        if role != "conv":
            continue
        m_in = 4 * m if name == "down" else m
        f = 2.0 * m * n * k * c
        byt = c * (n * k * wbytes + (m_in * k // conv_area(name, k)
                                     + m * n) * cost.ACT_BYTES)
        tot += max(f / peak_flops, byt / peak_bw)
    return tot


def conv_roofline(run, pm: dict, conv_s: float) -> float:
    """Least time of every conv-role site of one execution (UNet sites
    x evaluations run, VAE sites; ``pm`` as ``layers.per_module``) over
    ``conv_s``, the device time under the ``conv`` scope per execution,
    in %."""
    pf = run.peak["bf16_flops_per_s"]
    bw = run.peak["hbm_bytes_per_s"]
    least = (conv_min_seconds(pm["unet"], run.spec, run.cost, pf, bw)
             * pm["unet_evals"]
             + conv_min_seconds(pm["vae"], run.spec, run.cost, pf, bw))
    return 100.0 * least / conv_s

