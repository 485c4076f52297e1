"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to what the per-layer
metrics read.

The harness traces a sub-window of the measured window and, right after
the profiler starts, opens a ``bench.mark`` annotation at a known
``time.perf_counter`` reading; that puts the host clock the harness
stamps with and the trace's clock on one axis.

From the first TPU plane (``/device:TPU:0``), as a v5e trace lays it out:

* busy intervals: the union of the events on its ``XLA Ops`` line (the
  line nests: a layer scan's ``while`` op holds the ops of its body);
* time per Pallas kernel: the ops whose name is the kernel's
  (``%paged_decode_attention.9 = ... custom-call(...)``, ``KERNELS``);
* launches of the engine's steps: the engine jits ``functools.partial``
  objects, which the trace names ``jit__unknown(<hash>)``, so a launch on
  the ``XLA Modules`` line is named by the kernel it runs (``STEPS``: a
  launch that runs the paged decode kernel is a decode step) and
  otherwise by its module name (``jit__argmax``).

From the host plane: the harness's own ``bench.*`` spans, which label
the device's idle gaps with what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
import shutil

KERNELS = {
    # metric key -> names of the Pallas kernel's op in the trace
    "paged_decode": ("paged_decode_attention",),
    "paged_chunk_prefill": ("paged_chunk_prefill_attention",),
    "qkv_matmul": ("qkv_projection",),
}
STEPS = {"decode": "paged_decode", "prefill": "paged_chunk_prefill"}
DEVICE_PLANE = re.compile(r"^/device:TPU:0$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_SPAN = "bench."
MARK = "bench.mark"


def latest_xspace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return paths[-1]


def remove(log_dir: str) -> None:
    shutil.rmtree(log_dir, ignore_errors=True)


def module_name(name: str) -> str:
    """``jit__argmax(123)`` -> ``jit__argmax``."""
    return name.split("(")[0]


def op_name(name: str) -> str:
    """The op's HLO name without its instruction text and numbering:
    ``%paged_decode_attention.9 = bf16[...] custom-call(...)`` ->
    ``paged_decode_attention``; ``%copy.76.remat`` -> ``copy``."""
    head = name.split(" = ")[0].lstrip("%")
    return re.sub(r"(\.\d+)*(\.remat\d*)?$", "", head)


def kernel_of(op: str) -> str | None:
    for key, names in KERNELS.items():
        if op in names:
            return key
    return None


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, merged) -> float:
    return sum(max(0.0, min(a1, e) - max(a0, s)) for s, e in merged)


def _self_times(ops: list) -> list:
    """Each op's duration less that of the ops nested in it (same line,
    starting inside it): a ``while`` op keeps only its own time."""
    self_t = [d for _, _, _, _, d in ops]
    stack: list = []
    for i, (_, _, _, s, d) in enumerate(ops):
        while stack and ops[stack[-1]][3] + ops[stack[-1]][4] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= d
        stack.append(i)
    return self_t


@dataclasses.dataclass
class Summary:
    """One traced window, in seconds on the host's ``perf_counter`` axis."""
    host_t0: float
    host_t1: float
    busy: list            # merged device busy intervals [start, end]
    ops: list             # (op name, step, kernel key or None, start, dur)
    modules: list         # (step, start, dur)
    host: list            # (span name, start, dur)

    @property
    def window_s(self) -> float:
        return self.host_t1 - self.host_t0

    @property
    def busy_s(self) -> float:
        return _overlap(self.host_t0, self.host_t1, self.busy)

    def launches(self, step: str) -> list:
        """Device time of each launch of ``step`` (``decode``,
        ``prefill`` or a module name)."""
        return [d for m, s, d in self.modules if m == step]

    def kernel_s(self, key: str) -> float:
        return sum(d for _, _, k, _, d in self.ops if k == key)

    def host_spans(self, name: str) -> list:
        return [(s, d) for n, s, d in self.host if n == name]

    def uncovered(self, start: float, dur: float) -> float:
        """Part of a host interval with no device op running."""
        return dur - _overlap(start, start + dur, self.busy)

    def gaps(self) -> list:
        """Idle device intervals inside the window, longest first, each
        labelled by the host span that covers its midpoint."""
        edges = [[self.host_t0, self.host_t0]] + self.busy + \
            [[self.host_t1, self.host_t1]]
        out = []
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            a, b = max(e0, self.host_t0), min(s1, self.host_t1)
            if b <= a:
                continue
            mid = (a + b) / 2
            label = "none"
            for n, s, d in self.host:
                if n != MARK and s <= mid <= s + d:
                    label = n[len(HOST_SPAN):]
            out.append((label, b - a))
        return sorted(out, key=lambda g: -g[1])

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (self time, by step and op
        name) and the longest idle gaps."""
        by_op: dict = {}
        for (name, step, _, _, _), t in zip(self.ops, _self_times(self.ops)):
            label = f"{step}/{name}"
            by_op[label] = by_op.get(label, 0.0) + t
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps()[:top]]}


def load(path: str):
    """The ``ProfileData`` of an ``.xplane.pb`` file, or of a gzipped one."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            return ProfileData.from_serialized_xspace(fh.read())
    return ProfileData.from_file(path)


def summarize(path: str, h0: float, h1: float, mark_host: float) -> Summary:
    """Reduce the trace at ``path``.  ``mark_host``: the ``perf_counter``
    reading taken as the ``bench.mark`` span opened; [h0, h1] the traced
    window on the same clock."""
    return reduce(load(path).planes, h0, h1, mark_host)


def reduce(planes, h0: float, h1: float, mark_host: float) -> Summary:
    planes = list(planes)
    host = []
    mark_ns = None
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_SPAN):
                    host.append((ev.name, ev.start_ns, ev.duration_ns))
                    if ev.name == MARK and mark_ns is None:
                        mark_ns = ev.start_ns
    if mark_ns is None:
        raise ValueError("trace holds no bench.mark span")
    to_s = lambda ns: (ns - mark_ns) * 1e-9 + mark_host   # noqa: E731
    dev = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not dev:
        raise ValueError(f"no TPU device plane in {[p.name for p in planes]}")
    raw_mods, raw_ops = [], []
    for line in dev[0].lines:
        if line.name == MODULES_LINE:
            raw_mods = [(module_name(ev.name), to_s(ev.start_ns),
                         ev.duration_ns * 1e-9) for ev in line.events]
        elif line.name == OPS_LINE:
            raw_ops = [(op_name(ev.name), to_s(ev.start_ns),
                        ev.duration_ns * 1e-9) for ev in line.events]
    raw_mods.sort(key=lambda m: m[1])
    raw_ops.sort(key=lambda o: (o[1], -o[2]))
    # name each launch by the step kernel it runs, and each op by its launch
    modules, ops, i = [], [], 0
    for name, s, d in raw_mods:
        inside = []
        while i < len(raw_ops) and raw_ops[i][1] < s + d:
            if raw_ops[i][1] >= s:
                inside.append(raw_ops[i])
            else:
                ops.append((raw_ops[i][0], "none", kernel_of(raw_ops[i][0]),
                            raw_ops[i][1], raw_ops[i][2]))
            i += 1
        kernels = {kernel_of(o[0]) for o in inside}
        step = next((k for k, kern in STEPS.items() if kern in kernels), name)
        modules.append((step, s, d))
        ops += [(o[0], step, kernel_of(o[0]), o[1], o[2]) for o in inside]
    ops += [(o[0], "none", kernel_of(o[0]), o[1], o[2]) for o in raw_ops[i:]]
    ops.sort(key=lambda o: (o[3], -o[4]))
    keep = lambda s: h0 <= s <= h1                        # noqa: E731
    return Summary(
        host_t0=h0, host_t1=h1,
        busy=_union([(s, s + d) for _, s, d in raw_ops]),
        ops=[o for o in ops if keep(o[3])],
        modules=[m for m in modules if keep(m[1])],
        host=[(n, to_s(s), d * 1e-9) for n, s, d in host])
