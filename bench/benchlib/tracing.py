"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

The profiler writes an XSpace (``*.xplane.pb``). Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
that ran on the chip. The host plane ``/host:CPU`` holds the harness's own
``TraceAnnotation`` spans and JAX's dispatch spans on the Python thread.

From that the reduction takes:

  * the window: the harness's ``bench.window`` span on the host;
  * busy time: the union of device-op intervals inside the window, averaged
    over the chips used (idle share = 1 - busy / window);
  * time by program (the ``XLA Modules`` line: ``jit__scrub_rows``, ...) and
    by operation within it; an op event's name is its HLO text, so a Mosaic
    kernel appears as ``%<kernel>.<n> = <out shape> custom-call(<operand
    shapes>)`` with its shapes, from which the kernel's operations and bytes
    are computed;
  * idle gaps: the stretches of the window with no device op, each labelled
    with the innermost host span that covers its middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
WAVE_SPAN = "bench.wave"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ops whose events enclose other ops' events (a scan's while loop)
CONTAINERS = ("while", "conditional", "call")
_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def op_base(name: str) -> str:
    """'%ecc_matmul_2d.47 = f32[...] custom-call(...)' -> 'ecc_matmul_2d'."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.[0-9]+$", "", head)


def module_base(name: str) -> str:
    """'jit__scrub_rows(1152...)' -> 'jit__scrub_rows'."""
    return name.split("(", 1)[0]


DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1}


def shapes(name: str) -> tuple[list, list]:
    """(output shapes, operand shapes) of a custom-call event, each a list
    of (dtype, dims)."""
    _, rhs = name.split(" = ", 1)
    out_part, _, rest = rhs.partition(" custom-call(")
    operands = rest.split("custom_call_target=", 1)[0]
    parse = lambda t: [(d, tuple(int(x) for x in dims.split(",") if x)) for d, dims in _SHAPE.findall(t)]
    return parse(out_part), parse(operands)


@dataclasses.dataclass
class Reduced:
    window: tuple[int, int]  # ns, host clock
    n_devices: int
    busy_ns: float  # union of device-op intervals, mean over devices
    op_ns: dict  # "program/op" -> summed device ns (all devices), no containers
    module_ns: dict  # program -> summed device ns (all devices)
    kernels: list  # [(HLO text, ns)] of every Mosaic kernel event
    gaps: list  # [(start_ns, end_ns, host label)] on device 0, longest first

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / (self.window[1] - self.window[0])

    def kernel_events(self, kernel: str) -> list:
        """[(HLO text, ns)] of the events of one Mosaic kernel."""
        return [(n, ns) for n, ns in self.kernels if op_base(n) == kernel]


def load(trace_dir: str):
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(files[-1])


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce(pd, max_gaps: int = 10) -> Reduced:
    host_spans, window = [], None
    dev_ops, dev_modules = {}, {}
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW_SPAN:
                        window = (s, e)
                    host_spans.append((name, s, e))
        elif plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev_ops[plane.name] = list(_events(line))
                elif line.name == MODULES_LINE:
                    dev_modules[plane.name] = sorted(_events(line), key=lambda t: t[1])
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span on the host plane")
    if not dev_ops:
        raise ValueError("no device plane with an 'XLA Ops' line")
    lo, hi = window
    op_ns, module_ns, kernels, busy = {}, {}, [], []
    for dev in sorted(dev_ops):
        mods = [(module_base(n), s, e) for n, s, e in dev_modules.get(dev, [])]
        for m, s, e in mods:
            c = _clip([(s, e)], lo, hi)
            if c:
                module_ns[m] = module_ns.get(m, 0) + c[0][1] - c[0][0]
        starts = [s for _, s, _ in mods]
        ivs = []
        for name, s, e in dev_ops[dev]:
            c = _clip([(s, e)], lo, hi)
            if not c:
                continue
            ivs.append(c[0])
            ns = c[0][1] - c[0][0]
            base = op_base(name)
            if 'custom_call_target="tpu_custom_call"' in name:
                kernels.append((name, ns))
            if base in CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][0] if i >= 0 and mods[i][2] >= s else "?"
            key = f"{prog}/{base}"
            op_ns[key] = op_ns.get(key, 0) + ns
        busy.append(_union(ivs))
    busy_ns = sum(sum(e - s for s, e in u) for u in busy) / len(busy)
    # idle gaps on the first device, labelled by the host span around them
    gaps, t = [], lo
    for s, e in busy[0] + [[hi, hi]]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    inner = [sp for sp in host_spans if sp[0] not in (WINDOW_SPAN, WAVE_SPAN)]

    def label(g):
        mid = (g[0] + g[1]) / 2
        cover = [sp for sp in inner if sp[1] <= mid <= sp[2]]
        return min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover else "host: no span"

    gaps.sort(key=lambda g: g[0] - g[1])
    return Reduced(
        window=window,
        n_devices=len(busy),
        busy_ns=busy_ns,
        op_ns=op_ns,
        module_ns=module_ns,
        kernels=kernels,
        gaps=[(s, e, label((s, e))) for s, e in gaps[:max_gaps]],
    )


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most time
    and the longest idle gaps by host span, in seconds."""
    ops = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[k, v / 1e9] for k, v in ops],
        "idle_gaps": [[lab, (e - s) / 1e9] for s, e, lab in red.gaps[:top]],
    }
