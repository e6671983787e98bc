"""Reduction of a ``jax.profiler`` trace to numbers: code kept with
the benchmark so every PR computes the same number the same way.

Two steps. ``load`` turns an ``.xplane.pb`` into a plain structure
(``Trace``: per device the leaf operations and the program executions,
and the host's annotated spans), which is also what the recorded trace
beside the tests holds as JSON. Everything else is arithmetic on that
structure: busy and idle time, time per operation and per program,
idle gaps attributed to the host span open at the time, collectives
not hidden under compute.

Times are nanoseconds on the profiler's clock; results are seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Operations that only contain other operations: counting them would
# count their bodies twice.
_CONTAINERS = ('while', 'conditional', 'call', 'async-start', 'async-done')
_COLLECTIVES = ('all-reduce', 'all-gather', 'reduce-scatter', 'all-to-all',
                'collective-permute')


@dataclasses.dataclass
class Op:
    name: str          # the HLO instruction, e.g. ``fusion.12``
    start: float
    dur: float
    module: str = ''   # the HLO module (the jitted program)
    long_name: str = ''  # HLO text where the trace carries it

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Op]
    modules: List[Op]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    spans: List[Op]    # host spans the benchmark opened (name, start, dur)
    t0: float = 0.0
    t1: float = 0.0


def start(trace_dir: str):
    """Start the profiler (host spans on, Python tracer off) and open
    the ``bench.window`` span that marks the traced window; returns
    the span for ``stop``."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = jax.profiler.TraceAnnotation('bench.window')
    span.__enter__()
    return span


def stop(span) -> None:
    import jax
    span.__exit__(None, None, None)
    jax.profiler.stop_trace()


# -- loading -----------------------------------------------------------------


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    return paths[-1] if paths else None


def load(trace_dir: str, span_prefixes: Sequence[str] = ('engine.',
                                                         'train.',
                                                         'bench.')
         ) -> Optional[Trace]:
    """Read the newest trace under ``trace_dir``; None if there is
    none. Device planes are those named ``/device:TPU:<n>``; their
    ``XLA Ops`` line holds operations and ``XLA Modules`` programs."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: List[Device] = []
    spans: List[Op] = []
    for plane in data.planes:
        if plane.name.startswith('/device:') and 'TPU' in plane.name:
            ops: List[Op] = []
            modules: List[Op] = []
            for line in plane.lines:
                if line.name == 'XLA Ops':
                    for ev in line.events:
                        # On this chip an operation's event is named
                        # by its whole HLO text ('%fusion.7 = bf16[..]
                        # fusion(..)'): keep the instruction as the
                        # name and the head of the text for the shape.
                        head, sep, _ = ev.name.partition(' = ')
                        ops.append(Op(head.lstrip('%') if sep else ev.name,
                                      float(ev.start_ns),
                                      float(ev.duration_ns), '',
                                      ev.name[:160] if sep else ''))
                elif line.name == 'XLA Modules':
                    for ev in line.events:
                        modules.append(Op(ev.name, float(ev.start_ns),
                                          float(ev.duration_ns)))
            if ops or modules:
                devices.append(Device(plane.name, ops, modules))
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tuple(span_prefixes)):
                        spans.append(Op(ev.name, float(ev.start_ns),
                                        float(ev.duration_ns)))
    for dev in devices:
        _assign_modules(dev)
    tr = Trace(devices, spans)
    tr.t0, tr.t1 = window_of(tr)
    return tr


def _assign_modules(dev: Device) -> None:
    """Give each operation without a module the program execution
    that contains it in time."""
    mods = sorted(dev.modules, key=lambda m: m.start)
    if not mods:
        return
    import bisect
    starts = [m.start for m in mods]
    for op in dev.ops:
        if op.module:
            continue
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < mods[i].end:
            op.module = mods[i].name


def to_json(tr: Trace) -> Dict:
    return {'devices': [{'name': d.name,
                         'ops': [dataclasses.astuple(o) for o in d.ops],
                         'modules': [dataclasses.astuple(o)
                                     for o in d.modules]}
                        for d in tr.devices],
            'spans': [dataclasses.astuple(o) for o in tr.spans],
            't0': tr.t0, 't1': tr.t1}


def from_json(obj: Dict) -> Trace:
    tr = Trace([Device(d['name'], [Op(*o) for o in d['ops']],
                       [Op(*o) for o in d['modules']])
                for d in obj['devices']],
               [Op(*o) for o in obj['spans']], obj['t0'], obj['t1'])
    for dev in tr.devices:
        _assign_modules(dev)
    return tr


def load_json(path: str) -> Trace:
    with open(path) as f:
        return from_json(json.load(f))


# -- arithmetic --------------------------------------------------------------


def is_container(name: str) -> bool:
    return name.split('.')[0].lstrip('%') in _CONTAINERS


def is_collective(name: str) -> bool:
    base = name.lstrip('%')
    return base.startswith(_COLLECTIVES)


def leaf_ops(dev: Device) -> List[Op]:
    return [o for o in dev.ops if not is_container(o.name) and o.dur > 0]


def window_of(tr: Trace) -> Tuple[float, float]:
    """The traced window: from the ``bench.window`` span if the run
    opened one, else from the first to the last device operation."""
    for s in tr.spans:
        if s.name == 'bench.window':
            return s.start, s.end
    starts = [o.start for d in tr.devices for o in d.ops]
    ends = [o.end for d in tr.devices for o in d.ops]
    if not starts:
        return 0.0, 0.0
    return min(starts), max(ends)


def union(intervals: Iterable[Tuple[float, float]], t0: float,
          t1: float) -> List[Tuple[float, float]]:
    """Merged intervals clipped to [t0, t1]."""
    iv = sorted((max(a, t0), min(b, t1)) for a, b in intervals
                if b > t0 and a < t1)
    out: List[Tuple[float, float]] = []
    for a, b in iv:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_s(dev: Device, t0: float, t1: float) -> float:
    merged = union(((o.start, o.end) for o in leaf_ops(dev)), t0, t1)
    return sum(b - a for a, b in merged) / 1e9


def gaps(dev: Device, t0: float, t1: float) -> List[Tuple[float, float]]:
    merged = union(((o.start, o.end) for o in leaf_ops(dev)), t0, t1)
    out, cur = [], t0
    for a, b in merged:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        out.append((cur, t1))
    return out


def busy_and_window(tr: Trace) -> Tuple[float, float, float]:
    """(busy seconds averaged over the devices, busy seconds of the
    busiest device, window seconds)."""
    if not tr.devices or tr.t1 <= tr.t0:
        return 0.0, 0.0, 0.0
    each = [busy_s(d, tr.t0, tr.t1) for d in tr.devices]
    return sum(each) / len(each), max(each), (tr.t1 - tr.t0) / 1e9


def innermost_span(spans: Sequence[Op], t: float) -> str:
    """Name of the shortest span of the benchmark's that is open at
    ``t`` (``bench.window`` excepted), or ``unattributed``."""
    best: Optional[Op] = None
    for s in spans:
        if s.name != 'bench.window' and s.start <= t < s.end:
            if best is None or s.dur < best.dur:
                best = s
    return best.name if best is not None else 'unattributed'


def idle_by_span(tr: Trace, top: int = 10) -> List[List]:
    """Idle seconds of the devices (averaged over them), by the host
    span open when each gap began (what the host was doing when the
    device ran dry); longest first."""
    if not tr.devices:
        return []
    acc: Dict[str, float] = {}
    for dev in tr.devices:
        for a, b in gaps(dev, tr.t0, tr.t1):
            name = innermost_span(tr.spans, a)
            acc[name] = acc.get(name, 0.0) + (b - a) / 1e9
    n = len(tr.devices)
    rows = sorted(((k, v / n) for k, v in acc.items()),
                  key=lambda kv: -kv[1])
    return [[k, v] for k, v in rows[:top]]


_SHAPE = re.compile(r'=\s*\(?([a-z]+\d*)\[([\d,]*)\]')


def op_label(op: Op, programs: Sequence[Dict]) -> str:
    """``<program>/<op>_<dtype>_<shape>``: the instruction's name less
    its number, with the result's type and shape where the trace
    carries the HLO text."""
    base = re.sub(r'\.\d+$', '', op.name.lstrip('%'))
    label = base
    m = _SHAPE.search(op.long_name or '')
    if m:
        label = f'{base}_{m.group(1)}_{m.group(2).replace(",", "_")}_'
    return f'{program_of(op.module, programs)}/{label}'[:96]


def program_of(module: str, programs: Sequence[Dict]) -> str:
    """The benchmark's short name for a jitted program, by the first
    pattern of ``programs.json`` that matches its HLO module."""
    for p in programs:
        if re.search(p['match'], module or ''):
            return p['name']
    return (module or 'unknown')[:32]


def per_op_seconds(tr: Trace, programs: Sequence[Dict],
                   top: int = 10) -> List[List]:
    """Device seconds by operation label, averaged over the devices,
    within the window; most first."""
    if not tr.devices:
        return []
    acc: Dict[str, float] = {}
    for dev in tr.devices:
        for o in leaf_ops(dev):
            if o.end <= tr.t0 or o.start >= tr.t1:
                continue
            lab = op_label(o, programs)
            acc[lab] = acc.get(lab, 0.0) + o.dur / 1e9
    n = len(tr.devices)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / n] for k, v in rows]


def program_seconds(tr: Trace, programs: Sequence[Dict]
                    ) -> Dict[str, Tuple[float, int]]:
    """{program: (device seconds of its leaf operations averaged over
    the devices, executions on the first device)} within the window."""
    if not tr.devices:
        return {}
    acc: Dict[str, float] = {}
    for dev in tr.devices:
        for o in leaf_ops(dev):
            if o.end <= tr.t0 or o.start >= tr.t1:
                continue
            name = program_of(o.module, programs)
            acc[name] = acc.get(name, 0.0) + o.dur / 1e9
    n = len(tr.devices)
    runs: Dict[str, int] = {}
    for m in tr.devices[0].modules:
        if m.start >= tr.t0 and m.end <= tr.t1:
            name = program_of(m.name, programs)
            runs[name] = runs.get(name, 0) + 1
    return {k: (v / n, runs.get(k, 0)) for k, v in acc.items()}


def module_runs(tr: Trace, programs: Sequence[Dict], program: str
                ) -> List[float]:
    """Durations (seconds) of the whole executions of ``program`` on
    the first device that lie inside the window."""
    if not tr.devices:
        return []
    return [m.dur / 1e9 for m in tr.devices[0].modules
            if m.start >= tr.t0 and m.end <= tr.t1
            and program_of(m.name, programs) == program]


def ops_matching(tr: Trace, pattern: str, device: int = 0) -> List[Op]:
    if not tr.devices:
        return []
    rx = re.compile(pattern)
    return [o for o in leaf_ops(tr.devices[device])
            if o.start >= tr.t0 and o.end <= tr.t1
            and (rx.search(o.name) or rx.search(o.long_name or ''))]


def exposed_collective_s(dev: Device, t0: float, t1: float) -> float:
    """Seconds in which a collective ran on ``dev`` and no other
    operation did."""
    coll = union(((o.start, o.end) for o in leaf_ops(dev)
                  if is_collective(o.name)), t0, t1)
    other = union(((o.start, o.end) for o in leaf_ops(dev)
                   if not is_collective(o.name)), t0, t1)
    exposed = 0.0
    j = 0
    for a, b in coll:
        cur = a
        while j < len(other) and other[j][1] <= a:
            j += 1
        k = j
        while k < len(other) and other[k][0] < b:
            oa, ob = other[k]
            if oa > cur:
                exposed += oa - cur
            cur = max(cur, ob)
            k += 1
        if b > cur:
            exposed += b - cur
    return exposed / 1e9
