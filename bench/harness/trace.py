"""Reduction of a profiler trace to device time, idle time and kernel
time, on the profiler's own clock.

* The traced window is the span of the loop's ``bench.*`` host spans
  (they follow each other without gaps from the trace's start to the
  close).
* Busy time is the union of the device's op intervals (the
  ``XLA Ops`` line of each ``/device:TPU:<n>`` plane) within the window;
  idle share is 1 - busy / window.
* A program execution (``XLA Modules`` line) counts when it lies wholly
  in the window, runs the kernels below (so the engine's denoise
  program, not the small eager programs around it) and holds as many
  kernel events as the fullest such execution (a trace can lose the
  first events of the execution running as the profiler starts);
  kernel time is the summed duration of a kernel's op events inside
  those executions.
* Idle gaps are labelled with the host span that covers most of them.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

# A device op's name is its HLO instruction.  The Pallas kernels carry
# no name of their own there (every one is a ``tpu_custom_call`` from
# ``pallas_call``), so each is told by its operand types, which only it
# has: the Q8_0 matmul reads int8 weights, the Q3_K matmul packed uint8
# planes, flash attention three bfloat16 (batch*heads, seq, dim) blocks
# and returns a fourth.  A kernel whose event count disagrees with the
# calls its shapes give is left unread by the metric readers.
PALLAS = re.compile(r"^%\S+ = (\w+)\[[^\]]*\]\S* custom-call\((.*)\), "
                    r"custom_call_target=\"tpu_custom_call\"")
OPERAND = re.compile(r"(\w+)\[([\d,]*)\]")


def kernel_of(name: str) -> str | None:
    m = PALLAS.match(name)
    if m is None:
        return None
    out, ops = m.group(1), OPERAND.findall(m.group(2))
    types = [t for t, _ in ops]
    if "s8" in types:
        return "q8_matmul"
    if "u8" in types:
        return "q3k_matmul"
    if (out == "bf16" and len(ops) == 3 and types == ["bf16"] * 3
            and all(d.count(",") == 2 for _, d in ops)):
        return "flash_attention"
    return None


# Control-flow ops span the ops of their body on the same line: they
# count in busy time through their body alone.
CONTAINER = re.compile(r"^%(while|conditional|call)[.\s]")
OP_KIND = re.compile(r"^%([\w-]+?)(?:\.\d+)? = (\S+?\[[\d,]*\])")


def op_kind(name: str) -> str:
    """A short name for a device op: the kernel it runs, or its HLO
    instruction without its number, with its output type."""
    k = kernel_of(name)
    if k is not None:
        return k
    m = OP_KIND.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader sees."""
    win: Any
    trace: dict | None
    spec: dict
    mix: dict
    cost: Any
    peaks: dict
    kind: str

    @property
    def peak(self) -> dict:
        if self.kind not in self.peaks:
            raise KeyError(f"no peaks for device kind {self.kind!r}")
        return self.peaks[self.kind]


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    tot = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                tot += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        tot += cur_e - cur_s
    return tot


def merged(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_events(ops, modules, host, top: int = 10) -> dict:
    """``ops``/``modules``: per device, lists of ``(name, start_s,
    end_s)``; ``host``: ``(name, start_s, end_s)`` spans.  All on one
    clock.  ``None`` without host spans or device planes."""
    if not host or not ops:
        return None
    w0 = min(s for _, s, _ in host)
    w1 = max(e for _, _, e in host)
    window = w1 - w0
    busy = []
    kernel_s: dict[str, float] = {}
    kernel_n: dict[str, int] = {}
    op_s: dict[str, float] = {}
    complete = []
    module_s = []
    gaps = []
    for dev_ops, dev_mods in zip(ops, modules):
        iv = [(max(s, w0), min(e, w1)) for name, s, e in dev_ops
              if e > w0 and s < w1 and not CONTAINER.match(name)]
        busy.append(union_length(iv))
        for name, s, e in dev_ops:
            if e > w0 and s < w1 and not CONTAINER.match(name):
                kind = op_kind(name)
                op_s[kind] = op_s.get(kind, 0.0) + min(e, w1) - max(s, w0)
        mods = [(s, e) for _, s, e in dev_mods if s >= w0 and e <= w1]
        kops = [(kernel_of(name), s, e) for name, s, e in dev_ops]
        kops = [x for x in kops if x[0] is not None]
        per = {}                     # module -> (kernel counts, seconds)
        for k, s, e in kops:
            for ms, me in mods:
                if ms <= s and e <= me:
                    n, t = per.setdefault((ms, me), ({}, {}))
                    n[k] = n.get(k, 0) + 1
                    t[k] = t.get(k, 0.0) + (e - s)
                    break
        # A trace can lose a program's first events (the profiler was
        # still starting): keep the executions with the most kernel events.
        most = max((sum(n.values()) for n, _ in per.values()), default=0)
        whole = {m: v for m, v in per.items() if sum(v[0].values()) == most}
        complete.append(len(whole))
        module_s.append(sum(me - ms for ms, me in whole))
        for n, t in whole.values():
            for k in n:
                kernel_n[k] = kernel_n.get(k, 0) + n[k]
                kernel_s[k] = kernel_s.get(k, 0.0) + t[k]
        prev = w0
        for s, e in merged(iv):
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if w1 > prev:
            gaps.append((prev, w1))
    labelled = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        cover: dict[str, float] = {}
        for name, s, e in host:
            o = min(e, ge) - max(s, gs)
            if o > 0:
                cover[name] = cover.get(name, 0.0) + o
        label = max(cover, key=cover.get) if cover else "untraced"
        labelled.append([label, ge - gs])
    n_dev = max(1, len(ops))
    return {
        "window_s": window,
        "busy_s": sum(busy) / n_dev,
        "modules": min(complete) if complete else 0,
        "module_s": module_s,
        "kernel_s": kernel_s,
        "kernel_n": kernel_n,
        "breakdown": {
            # by kind: a kernel, or an HLO op and its output type
            "device_ops": [[n, s] for n, s in sorted(
                op_s.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": labelled},
    }


def read_xplane(path: str, chips: int = 1):
    """``(ops, modules, host)`` in seconds from an ``.xplane.pb``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    ops, modules, host = [], [], []
    devices = sorted((p for p in pd.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)[:chips]
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops.append([(e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in lines[OPS_LINE].events])
        modules.append([(e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in lines[MODULES_LINE].events])
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(HOST_PREFIX):
                    host.append((e.name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9))
    return ops, modules, host


def reduce(path: str, chips: int = 1) -> dict | None:
    return reduce_events(*read_xplane(path, chips))
