"""Reduction of a JAX profiler trace to device metrics.

Reads the ``.xplane.pb`` a ``jax.profiler`` trace writes, with nothing
but ``jax.profiler.ProfileData``:

* device operations: on a TPU the events of each ``/device:TPU:<n>``
  plane's ``XLA Ops`` line; on the CPU backend (used by the tests) the
  host events that carry an ``hlo_op`` stat, keyed by their
  ``device_ordinal``;
* program executions: the ``XLA Modules`` line of a device plane, or on
  the CPU the operations grouped by (``hlo_module``, ``run_id``);
* host annotations: events on the host planes named by the benchmark's
  own ``jax.profiler.TraceAnnotation`` labels.

``reduce_trace`` gives, inside a window, each device's busy time (the
union of its operation intervals), the idle share, the operations that
took most time, the step program's device time per execution (the union
of its operations, per execution, on the busiest device) and the longest
idle gaps, each labelled by the innermost benchmark annotation that
covers its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ID_SUFFIX = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][\w-]*)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")


@dataclasses.dataclass
class Trace:
    ops: dict            # device -> list of (name, module, start_ns, end_ns)
    executions: dict     # device -> list of (module, start_ns, end_ns)
    annotations: list    # (name, start_ns, end_ns) on the host


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def module_name(name: str) -> str:
    return _ID_SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """``name shape opcode`` of an HLO instruction's text (a TPU trace
    names each operation by its whole instruction, layouts and operands
    included); other names as they are."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    m = _OPCODE.search(rhs)
    if m is None:
        return lhs.lstrip("%")
    shape = _LAYOUT.sub("", rhs[:m.start(1)]).strip()
    if len(shape) > 48:
        shape = shape[:45] + "..."
    return f"{lhs.lstrip('%')} {shape} {m.group(1)}"


def load(path: str, annotation_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict = {}
    executions: dict = {}
    annotations: list = []
    cpu_runs: dict = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "NONCORE" not in plane.name.upper():
            dev = plane.name.split("/device:")[-1]
            for line in plane.lines:
                if line.name == OPS_LINE:
                    lst = ops.setdefault(dev, [])
                    for ev in line.events:
                        st = _stats(ev)
                        lst.append((op_name(ev.name),
                                    str(st.get("hlo_module", "")),
                                    float(ev.start_ns),
                                    float(ev.start_ns + ev.duration_ns)))
                elif line.name == MODULES_LINE:
                    lst = executions.setdefault(dev, [])
                    for ev in line.events:
                        lst.append((module_name(ev.name), float(ev.start_ns),
                                    float(ev.start_ns + ev.duration_ns)))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(annotation_prefix):
                    annotations.append((ev.name, float(ev.start_ns),
                                        float(ev.start_ns + ev.duration_ns)))
                    continue
                st = _stats(ev)
                if "hlo_op" not in st:
                    continue
                dev = f"CPU:{st.get('device_ordinal', 0)}"
                mod = str(st.get("hlo_module", ""))
                t0 = float(ev.start_ns)
                t1 = t0 + float(ev.duration_ns)
                ops.setdefault(dev, []).append((ev.name, mod, t0, t1))
                key = (dev, mod, st.get("run_id", 0))
                a, b = cpu_runs.get(key, (t0, t1))
                cpu_runs[key] = (min(a, t0), max(b, t1))
    for (dev, mod, _), (a, b) in cpu_runs.items():
        executions.setdefault(dev, []).append((mod, a, b))
    for lst in list(ops.values()) + list(executions.values()):
        lst.sort(key=lambda e: e[-2])
    annotations.sort(key=lambda e: e[1])
    return Trace(ops=ops, executions=executions, annotations=annotations)


def merge(intervals: list) -> np.ndarray:
    """Union of (start, end) intervals as a sorted (k, 2) array."""
    if not intervals:
        return np.zeros((0, 2))
    iv = np.asarray(sorted(intervals), np.float64)
    out = [iv[0].copy()]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append(np.array([a, b]))
    return np.asarray(out)


def clip(merged: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if merged.size == 0:
        return merged
    m = merged.copy()
    m[:, 0] = np.maximum(m[:, 0], lo)
    m[:, 1] = np.minimum(m[:, 1], hi)
    return m[m[:, 1] > m[:, 0]]


class Labeller:
    """Innermost (shortest) annotation covering a time. Annotations of
    one name never overlap each other, so each name is one sorted
    interval list searched by bisection."""

    def __init__(self, annotations: list, default: str):
        self.default = default
        by_name: dict = {}
        for name, a, b in annotations:
            by_name.setdefault(name, []).append((a, b))
        self.names = {n: np.asarray(sorted(iv)) for n, iv in by_name.items()}

    def __call__(self, t: float) -> str:
        best, best_len = self.default, np.inf
        for name, iv in self.names.items():
            i = np.searchsorted(iv[:, 0], t, "right") - 1
            if i >= 0 and iv[i, 1] >= t and iv[i, 1] - iv[i, 0] < best_len:
                best, best_len = name, iv[i, 1] - iv[i, 0]
        return best


def reduce_trace(tr: Trace, window: tuple[float, float] | None = None,
                 step_module: str | None = None, top: int = 10,
                 gap_default: str = "outside the benchmark's annotations"
                 ) -> dict:
    """Device metrics of ``tr`` inside ``window`` (ns; default: from the
    first to the last device operation).

    ``step_module`` names the step program; by default it is the module
    with the most device time. Returns busy seconds per device and their
    mean, the window's seconds, the idle share, the top operations
    (seconds summed over devices, divided by the device count), the step
    program's executions and device seconds per execution on the busiest
    device, and the ``top`` longest idle gaps with their labels.
    """
    devices = sorted(d for d, evs in tr.ops.items() if evs)
    if not devices:
        raise ValueError("the trace holds no device operation")
    if window is None:
        lo = min(tr.ops[d][0][2] for d in devices)
        hi = max(max(e[3] for e in tr.ops[d]) for d in devices)
    else:
        lo, hi = window
    win_s = (hi - lo) * 1e-9
    busy, gaps, op_time, mod_time = {}, [], {}, {}
    for d in devices:
        evs = [e for e in tr.ops[d] if e[3] > lo and e[2] < hi]
        m = clip(merge([(e[2], e[3]) for e in evs]), lo, hi)
        busy[d] = float((m[:, 1] - m[:, 0]).sum()) * 1e-9 if m.size else 0.0
        edges = np.concatenate([[lo], m.reshape(-1), [hi]]).reshape(-1, 2)
        for a, b in edges:
            if b > a:
                gaps.append((b - a, a, b, d))
        for name, mod, a, b in evs:
            dur = (min(b, hi) - max(a, lo)) * 1e-9
            op_time[name] = op_time.get(name, 0.0) + dur
        for mod, a, b in tr.executions.get(d, []):
            if b > lo and a < hi:
                mod_time[mod] = mod_time.get(mod, 0.0) + (b - a) * 1e-9
    n_dev = len(devices)
    if step_module is None and mod_time:
        step_module = max(mod_time, key=mod_time.get)
    per_exec, n_exec = {}, 0
    for d in devices:
        runs = [(a, b) for mod, a, b in tr.executions.get(d, [])
                if mod == step_module and a >= lo and b <= hi]
        if not runs:
            continue
        ops_iv = [(e[2], e[3]) for e in tr.ops[d]]
        starts = np.asarray([iv[0] for iv in ops_iv])
        total = 0.0
        for a, b in runs:
            i0, i1 = np.searchsorted(starts, [a, b])
            inside = [(max(s, a), min(e, b)) for s, e in ops_iv[i0:i1]]
            m = merge(inside)
            total += float((m[:, 1] - m[:, 0]).sum()) if m.size else 0.0
        per_exec[d] = total * 1e-9 / len(runs)
        n_exec = max(n_exec, len(runs))
    gaps.sort(reverse=True)
    label = Labeller(tr.annotations, gap_default)
    top_gaps = [[label(0.5 * (a + b)), dur * 1e-9]
                for dur, a, b, _ in gaps[:top]]
    by_label: dict = {}
    for dur, a, b, _ in gaps:
        lab = label(0.5 * (a + b))
        by_label[lab] = by_label.get(lab, 0.0) + dur * 1e-9 / n_dev
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {
        "devices": devices,
        "busy_s": busy,
        "busy_s_mean": sum(busy.values()) / n_dev,
        "window_s": win_s,
        "idle_share": 1.0 - sum(busy.values()) / n_dev / win_s,
        "top_ops": [[name, t / n_dev] for name, t in ops_sorted[:top]],
        "step_module": step_module,
        "step_executions": n_exec,
        "step_device_s": max(per_exec.values()) if per_exec else None,
        "idle_gaps": top_gaps,
        "idle_by_label": sorted(by_label.items(), key=lambda kv: -kv[1]),
    }
