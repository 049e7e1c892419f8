"""Reduce a profiler trace to device busy time and a breakdown.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Each accelerator is a plane named
``/device:TPU:<n>``; the operations it ran are the events of its
``XLA Ops`` line.  Host threads are lines of the ``/host:CPU`` plane, and the
benchmark's own spans (``jax.profiler.TraceAnnotation`` around submit and
collect) are events there, on the same clock.

* Device busy: the union of the operation intervals of each device,
  averaged over the devices.
* Idle share: 1 − busy / window, where the window is the traced wall time.
* Breakdown: the device operations that took the most time (summed by
  name), and the longest idle gaps between operations, each named by the
  benchmark span open on the host at the gap's midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
from pathlib import Path

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
NO_SPAN = "no bench span"
TOP = 10


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    device_ops: list          # [(name, seconds)], longest first
    idle_gaps: list           # [(name, seconds)], longest first
    n_devices: int

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.device_ops[:TOP]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:TOP]]}


def merge_intervals(iv: np.ndarray) -> np.ndarray:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield line.name, ev


def reduce_events(device_ops: dict[str, list[tuple[str, float, float]]],
                  host_spans: list[tuple[str, float, float]],
                  window_s: float) -> Reduced:
    """``device_ops`` maps a device to its (name, start_ns, duration_ns)
    operations; ``host_spans`` are (name, start_ns, duration_ns) benchmark
    spans; ``window_s`` the traced wall time."""
    busy, by_name, gaps = [], {}, []
    spans = sorted(host_spans, key=lambda s: s[1])
    for ops in device_ops.values():
        iv = np.asarray([(s, s + d) for _, s, d in ops], np.float64
                        ).reshape(-1, 2)
        merged = merge_intervals(iv)
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])) * 1e-9)
        for name, _, d in ops:
            by_name[name] = by_name.get(name, 0.0) + d * 1e-9
        for (_, e0), (s1, _) in zip(merged[:-1], merged[1:]):
            mid = 0.5 * (e0 + s1)
            open_spans = [n for n, s, d in spans if s <= mid <= s + d]
            gaps.append((open_spans[-1] if open_spans else NO_SPAN,
                         float(s1 - e0) * 1e-9))
    n = max(1, len(device_ops))
    return Reduced(busy_s=sum(busy) / n, window_s=float(window_s),
                   device_ops=sorted(by_name.items(), key=lambda x: -x[1]),
                   idle_gaps=sorted(gaps, key=lambda x: -x[1]),
                   n_devices=len(device_ops))


def op_name(module: str, hlo: str) -> str:
    """``jit_search/%fusion.3`` from a module event's name
    (``jit_search(9537…)``) and an op event's HLO text
    (``%fusion.3 = f32[…] fusion(…)``)."""
    return f"{module.split('(')[0]}/{hlo.split(' = ')[0].strip()}"


def _device_ops(plane) -> list[tuple[str, float, float]]:
    modules = sorted((float(ev.start_ns), ev.name)
                     for _, ev in _events(plane, MODULES_LINE))
    starts = np.asarray([s for s, _ in modules])
    out = []
    for _, ev in _events(plane, OPS_LINE):
        i = int(np.searchsorted(starts, float(ev.start_ns), "right")) - 1
        module = modules[i][1] if i >= 0 else "?"
        out.append((op_name(module, ev.name), float(ev.start_ns),
                    float(ev.duration_ns)))
    return out


def read_xplane(path: str | Path):
    """(device ops by device, benchmark host spans) of one xplane file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    device_ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            device_ops[plane.name] = _device_ops(plane)
        elif plane.name == HOST_PLANE:
            spans += [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for _, ev in _events(plane)
                      if ev.name.startswith(SPAN_PREFIX)]
    return device_ops, spans


def reduce_dir(log_dir: str | Path, window_s: float) -> Reduced:
    """Reduce the one trace the profiler wrote under ``log_dir``."""
    files = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    device_ops, spans = read_xplane(files[-1])
    if not device_ops:
        raise ValueError(f"{files[-1]} holds no {DEVICE_PREFIX}* plane")
    return reduce_events(device_ops, spans, window_s)
