"""Device trace: recording a window, and reducing it to times.

``start``/``stop`` wrap the JAX profiler; ``window_mark`` writes a host
annotation around the window, which puts the host's clock and the device's
events on one time line. ``load`` reads the ``.xplane.pb`` the profiler
wrote into a :class:`DeviceTrace`: the device's programs (line ``XLA
Modules``) and operations (line ``XLA Ops``), in seconds from the window's
start. The reduction works on that plain form, which a test can hold.

Names, as a v5e trace shows them: a program is ``jit_<function>(<hash>)``
and an operation is its HLO text, ``%<name>.<n> = <shape> <opcode>(...)``.
The BSR kernel is a custom call named after its jitted wrapper,
``bsr_matmul_stacked``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW_MARK = "bench.window"
# operations that hold others: their time is their body's
CONTROL_OPS = ("while", "conditional", "call")
BSR_KERNEL = "bsr_matmul_stacked"
DECODE_PROGRAM = "jit_decode_step_paged"
PREFILL_PROGRAM = "jit_prefill_last"


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


@contextlib.contextmanager
def window_mark():
    import jax

    with jax.profiler.TraceAnnotation(WINDOW_MARK):
        yield


_OP = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)?(?: = |$)")
_PROGRAM = re.compile(r"^(.*?)(?:\(\d+\))?$")


def op_name(hlo_text: str) -> str:
    """``%bsr_matmul_stacked.13 = f32[..] custom-call(..)`` ->
    ``bsr_matmul_stacked``."""
    m = _OP.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ", 1)[0]


def program_name(name: str) -> str:
    """``jit_decode_step_paged(8381848597439906979)`` ->
    ``jit_decode_step_paged``."""
    return _PROGRAM.match(name).group(1)


@dataclasses.dataclass
class DeviceTrace:
    """Device events in seconds from the window's start."""

    programs: List[Tuple[str, float, float]]   # (name, start, end)
    ops: List[Tuple[str, float, float]]

    @property
    def n_ops(self) -> int:
        return len(self.ops)

    def _leaf_ops(self):
        return [o for o in self.ops if o[0] not in CONTROL_OPS]

    def busy_intervals(self, t0: float, t1: float) -> List[Tuple[float,
                                                                 float]]:
        """Union of the intervals in which an operation ran, in [t0, t1]."""
        iv = sorted((max(s, t0), min(e, t1)) for _, s, e in self.ops
                    if e > t0 and s < t1)
        out: List[List[float]] = []
        for s, e in iv:
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [(s, e) for s, e in out]

    def busy_s(self, t0: float, t1: float) -> float:
        return float(sum(e - s for s, e in self.busy_intervals(t0, t1)))

    def program_times(self, name: str) -> List[float]:
        """Durations of each run of the program ``name``."""
        return [e - s for n, s, e in self.programs if n == name]

    def op_time(self, name: str, within: Optional[str] = None) -> float:
        """Summed time of operation ``name``, only inside runs of program
        ``within`` when given."""
        ops = [(s, e) for n, s, e in self.ops if n == name]
        if within is None:
            return float(sum(e - s for s, e in ops))
        runs = sorted((s, e) for n, s, e in self.programs if n == within)
        if not runs:
            return 0.0
        starts = np.array([s for s, _ in runs])
        ends = np.array([e for _, e in runs])
        tot = 0.0
        for s, e in ops:
            i = int(np.searchsorted(starts, s, side="right")) - 1
            if i >= 0 and e <= ends[i] + 1e-9:
                tot += e - s
        return float(tot)

    def kernel_time(self, within: Optional[str] = None) -> float:
        """Summed time of the BSR kernel's events (inside runs of program
        ``within`` when given). Raises when the steps ran but no event
        matched the kernel's name: a renamed kernel must not leave its
        metrics silent."""
        t = self.op_time(BSR_KERNEL, within)
        ran = [within] if within else [DECODE_PROGRAM, PREFILL_PROGRAM]
        if t <= 0 and any(self.program_times(p) for p in ran):
            raise ValueError(
                f"{' / '.join(ran)} ran but no device event matched the BSR "
                f"kernel's name {BSR_KERNEL!r}")
        return t

    def breakdown(self, spans: Optional[Sequence[tuple]], window_s: float,
                  top: int = 10) -> dict:
        """The operations that took most time, and the longest idle gaps,
        each named by the deepest host phase span that covers its
        middle ("no phase" when none does)."""
        tot: Dict[str, float] = {}
        for n, s, e in self._leaf_ops():
            if e > 0.0 and s < window_s:
                tot[n] = tot.get(n, 0.0) + (min(e, window_s) - max(s, 0.0))
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        busy = self.busy_intervals(0.0, window_s)
        edges = [0.0] + [x for iv in busy for x in iv] + [window_s]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        named = []
        for s, e in gaps:
            mid = 0.5 * (s + e)
            cover = [(se - ss, n) for n, ss, se, *_ in (spans or ())
                     if ss <= mid <= se]
            named.append([min(cover)[1] if cover else "no phase", e - s])
        return {"device_ops": [[n, t] for n, t in ops], "idle_gaps": named}

    @classmethod
    def from_json(cls, d: dict) -> "DeviceTrace":
        return cls([tuple(p) for p in d["programs"]],
                   [tuple(o) for o in d["ops"]])


def from_xspace(pd, device_id: int = 0) -> DeviceTrace:
    """Reduce a ``jax.profiler.ProfileData`` to a DeviceTrace."""
    mark = None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_MARK:
                        mark = (ev.start_ns, ev.end_ns)
    if mark is None:
        raise ValueError(f"trace has no {WINDOW_MARK!r} annotation")
    dev = pd.find_plane_with_name(f"/device:TPU:{device_id}")
    if dev is None:
        raise ValueError(f"trace has no plane /device:TPU:{device_id}")
    t0 = mark[0]
    programs, ops = [], []
    for line in dev.lines:
        if line.name == "XLA Modules":
            programs = [(program_name(e.name), (e.start_ns - t0) * 1e-9,
                         (e.end_ns - t0) * 1e-9) for e in line.events]
        elif line.name == "XLA Ops":
            ops = [(op_name(e.name), (e.start_ns - t0) * 1e-9,
                    (e.end_ns - t0) * 1e-9) for e in line.events]
    if not ops:
        raise ValueError("trace has no device operations")
    return DeviceTrace(programs, ops)


def load(log_dir: str, device_id: int = 0) -> DeviceTrace:
    import jax

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_xspace(jax.profiler.ProfileData.from_file(files[-1]),
                       device_id)
