"""From a profiler trace and a compiled program to the numbers the per-layer
metrics read.

* Busy time is the union of the device-operation intervals inside the traced
  window (the harness's ``bench.window`` host span), averaged over devices.
* Kernel time is the sum of the device durations of a kernel's events; each
  event is matched by its HLO instruction name to the ``tpu_custom_call`` of
  the compiled program, whose operand shapes give that call's work.
* Loop and call instructions are left out: their events span their bodies'
  ops, which have events of their own.
* Each idle gap inside the window is named by the harness host span
  (``bench.batch``, ``bench.dispatch``, ``bench.wait``, ``bench.drain``) that
  overlaps it most.

The functions on plain event lists carry the arithmetic and are tested on
hand-built events; ``load_profile`` adapts ``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import dataclasses
import re

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.batch", "bench.dispatch", "bench.wait", "bench.drain")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def clip(ev: Event, lo: float, hi: float) -> Event | None:
    s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
    return Event(ev.name, s, e - s) if e > s else None


def union(intervals) -> list:
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def gaps(busy, lo: float, hi: float) -> list:
    """The parts of ``[lo, hi]`` that no interval of ``busy`` covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def overlap(a, b) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def name_gap(gap, host_spans) -> str:
    """The host span that overlaps ``gap`` most, or ``other``."""
    best, name = 0.0, "other"
    for ev in host_spans:
        o = overlap(gap, (ev.start_ns, ev.end_ns))
        if o > best:
            best, name = o, ev.name
    return name


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over devices
    op_s: dict  # op name -> seconds, summed over devices
    op_calls: dict  # op name -> number of events
    idle_gaps: list  # [(host span name, seconds)], longest first

    def top_ops(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.op_s.items()), key=lambda kv: -kv[1])[:n]


def reduce_events(device_ops: list, host_spans: list, window: tuple) -> Summary:
    """``device_ops``: one list of op ``Event`` per device; ``host_spans``:
    the harness's host ``Event`` s; ``window``: ``(start_ns, end_ns)``."""
    lo, hi = window
    if hi <= lo or not device_ops:
        raise ValueError("empty traced window or no device")
    busy_total, op_s, op_calls, all_gaps = 0.0, {}, {}, []
    spans = [e for e in host_spans if e.name in HOST_SPANS]
    for ops in device_ops:
        inside = [c for c in (clip(e, lo, hi) for e in ops) if c is not None]
        merged = union((e.start_ns, e.end_ns) for e in inside)
        busy_total += sum(e - s for s, e in merged)
        for e in inside:
            op_s[e.name] = op_s.get(e.name, 0.0) + e.dur_ns * 1e-9
            op_calls[e.name] = op_calls.get(e.name, 0) + 1
        all_gaps += [(name_gap(g, spans), (g[1] - g[0]) * 1e-9) for g in gaps(merged, lo, hi)]
    all_gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / len(device_ops),
                   op_s=op_s, op_calls=op_calls, idle_gaps=all_gaps)


def load_profile(path: str) -> Summary:
    """Reduce one ``.xplane.pb`` file: device planes ``/device:TPU:<n>``, their
    ``XLA Ops`` line, and the harness's host spans from any host line."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    # a while loop's (or a call's) event spans its body's
                    # ops, which have events of their own
                    ops += [Event(_op_name(ev.name), ev.start_ns, ev.duration_ns) for ev in line.events
                            if not _CONTAINER.search(ev.name)]
            device_ops.append(ops)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host += [Event(ev.name, ev.start_ns, ev.duration_ns) for ev in line.events
                         if ev.name.startswith("bench.")]
    windows = [e for e in host if e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    return reduce_events(device_ops, host, (windows[0].start_ns, windows[0].end_ns))


_EVENT = re.compile(r"^%?(?P<name>[\w.\-]+) = ")
_CONTAINER = re.compile(r"[\]})] (while|conditional|call)\(")


def _op_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its HLO instruction's text; keep the
    instruction name (``%fusion.3 = f32[4] fusion(...)`` -> ``fusion.3``)."""
    m = _EVENT.match(text)
    return m.group("name") if m else text


# ------------------------- kernel calls in the HLO -------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<type>.*?)\s+custom-call\(")
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16|f8e4m3fn|f8e5m2)\[([\d,]*)\]")


def _braced(text: str, key: str) -> str:
    """The text inside ``key={...}`` with nested braces."""
    i = text.find(key + "={")
    if i < 0:
        return ""
    i += len(key) + 2
    depth, j = 1, i
    while j < len(text) and depth:
        depth += {"{": 1, "}": -1}.get(text[j], 0)
        j += 1
    return text[i:j - 1]


def shapes(text: str) -> list:
    return [(dt, tuple(int(d) for d in dims.split(",") if d)) for dt, dims in _SHAPE.findall(text)]


def kernel_calls(hlo_text: str, prefix: str = "panther_") -> dict:
    """``{instruction name: {"operands": [(dtype, shape)], "result": [...]}}``
    for every ``tpu_custom_call`` whose name starts with ``prefix``."""
    calls = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _INSTR.match(line)
        if not m or not m.group("name").startswith(prefix):
            continue
        calls[m.group("name")] = {
            "operands": shapes(_braced(line, "operand_layout_constraints")),
            "result": shapes(m.group("type")),
        }
    return calls


def family(name: str) -> str:
    """``panther_opa_fused.12`` -> ``panther_opa_fused``."""
    return re.sub(r"\.\d+$", "", name)


def roofline_share(summary: Summary, calls: dict, match, work, peaks: dict) -> float | None:
    """Summed least time of every traced call of the kernels ``match``
    selects (a predicate on the family name) over their summed device time,
    in percent; ``work(call) -> (ops, bytes, peak key)``. None when no such
    call ran in the window."""
    least = spent = 0.0
    for name, secs in summary.op_s.items():
        call = calls.get(name)
        if call is None or not match(family(name)):
            continue
        ops, nbytes, peak = work(call)
        n = summary.op_calls[name]
        least += n * max(ops / peaks[peak], nbytes / peaks["hbm_bytes_per_s"])
        spent += secs
    return 100.0 * least / spent if spent > 0 else None
