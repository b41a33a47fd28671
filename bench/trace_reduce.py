"""From a `jax.profiler` capture (``*.xplane.pb``) to device metrics.

What it reads:

  * device planes (``/device:TPU:<n>``): the ``XLA Ops`` line, one event
    per operation that ran, and the ``XLA Modules`` line, one event per
    program execution, named after the jitted function (``jit_pool_step``);
  * the host plane: the program's `TraceAnnotation` spans, the events with
    dotted names (``sched.pool_step``, ``engine.prefill_chunk``); the
    runtime's own events there (``MemoryAllocation``, ...) are left out.

What it gives (`Reduced`):

  * ``busy_s``: the union of operation intervals, averaged over the chips;
  * per program: executions and device seconds (module events);
  * per operation (HLO instruction name without its number): device
    seconds less those of the operations nested in it;
  * per execution of a program: a kernel's calls and device seconds inside
    it (`kernel_calls`);
  * idle gaps: each interval inside the capture in which no operation ran,
    named by the innermost host span that covers its middle.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")  # sched.pool_step, ...


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    end_ns: float
    device: int = 0


@dataclasses.dataclass
class Reduced:
    n_devices: int
    busy_s: float                   # mean over devices
    programs: dict[str, list]       # name -> [executions, device seconds]
    ops: dict[str, float]           # short op name -> self seconds, all devices
    gaps: list[tuple[float, str]]   # (seconds, host span) longest first
    op_list: list[Op]               # by start time
    executions: list[Op]            # program executions, by start time

    def program_seconds(self, pattern: str) -> tuple[int, float]:
        """(executions, device seconds) of programs whose name matches."""
        n, t = 0, 0.0
        for name, (k, s) in self.programs.items():
            if re.search(pattern, name):
                n, t = n + k, t + s
        return n, t

    def kernel_calls(self, kernel: str, program: str
                     ) -> list[tuple[int, float]]:
        """(calls, device seconds) of the kernel in each execution of a
        program whose name matches, in time order: the operations on the
        execution's device, within its interval, whose name holds the
        kernel's.  A Pallas call's instruction is named after its jitted
        wrapper, ``mxint4_matmul_pallas``, or ``vmap_jit_mxint4_matmul_
        pallas__`` where the wrapper runs under `jax.vmap`."""
        starts = [op.start_ns for op in self.op_list]
        out = []
        for ex in self.executions:
            if not re.search(program, ex.name):
                continue
            n, t = 0, 0.0
            i = bisect.bisect_left(starts, ex.start_ns)
            while i < len(starts) and starts[i] < ex.end_ns:
                op = self.op_list[i]
                if (op.device == ex.device and op.end_ns <= ex.end_ns
                        and kernel in short_op(op.name)):
                    n += 1
                    t += (op.end_ns - op.start_ns) * 1e-9
                i += 1
            out.append((n, t))
        return out

    def top_ops(self, k: int = 10) -> list[list]:
        return [[n, s] for n, s in sorted(self.ops.items(),
                                          key=lambda kv: -kv[1])[:k]]


def _base(name: str) -> str:
    """``jit_pool_step(1234)`` -> ``jit_pool_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def short_op(name: str) -> str:
    """An operation's event name is its HLO instruction, e.g.
    ``%mxint4_matmul_pallas.45 = f32[...] custom-call(...)``: keep the
    instruction's name without its number (``mxint4_matmul_pallas``)."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _self_seconds(ops: list[Op]) -> dict[str, float]:
    """Device seconds per short op name, each op less the ops nested in it
    (a ``while`` holds its loop body)."""
    out: dict[str, float] = collections.defaultdict(float)
    stack: list[list] = []                  # [op, seconds of children]
    for op in sorted(ops, key=lambda o: (o.start_ns, -o.end_ns)):
        while stack and stack[-1][0].end_ns <= op.start_ns:
            done, kids = stack.pop()
            out[short_op(done.name)] += (done.end_ns - done.start_ns
                                         - kids) * 1e-9
        if stack:
            stack[-1][1] += op.end_ns - op.start_ns
        stack.append([op, 0.0])
    for done, kids in stack:
        out[short_op(done.name)] += (done.end_ns - done.start_ns - kids) * 1e-9
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_profile(pd) -> Reduced:
    """Reduce a `jax.profiler.ProfileData`."""
    devices, host_spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods, d = [], [], len(devices)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [Op(ev.name, ev.start_ns, ev.end_ns, d)
                           for ev in line.events]
                elif line.name == MODULES_LINE:
                    mods = [Op(_base(ev.name), ev.start_ns, ev.end_ns, d)
                            for ev in line.events]
            devices.append((ops, mods))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans += [(ev.start_ns, ev.end_ns, ev.name)
                               for ev in line.events
                               if ANNOTATION.match(ev.name)]
    if not devices or not any(ops for ops, _ in devices):
        raise ValueError("the capture holds no device operations")

    programs: dict[str, list] = collections.defaultdict(lambda: [0, 0.0])
    op_secs: dict[str, float] = collections.defaultdict(float)
    busy, all_ops, executions = [], [], []
    for ops, mods in devices:
        for m in mods:
            programs[m.name][0] += 1
            programs[m.name][1] += (m.end_ns - m.start_ns) * 1e-9
        executions += mods
        for name, sec in _self_seconds(ops).items():
            op_secs[name] += sec
        u = _union([(op.start_ns, op.end_ns) for op in ops])
        busy.append(sum(e - s for s, e in u) * 1e-9)
        all_ops += ops

    ops0 = devices[0][0]
    u0 = _union([(op.start_ns, op.end_ns) for op in ops0])
    gaps = []
    for (_, e), (s, _) in zip(u0, u0[1:]):
        mid = 0.5 * (e + s)
        covering = [h for h in host_spans if h[0] <= mid <= h[1]]
        # Innermost span: the one that started last.
        name = (max(covering, key=lambda h: h[0])[2] if covering
                else "unannotated host work")
        gaps.append(((s - e) * 1e-9, name))
    gaps.sort(key=lambda g: -g[0])
    return Reduced(n_devices=len(devices),
                   busy_s=sum(busy) / len(busy),
                   programs=dict(programs), ops=dict(op_secs), gaps=gaps,
                   op_list=sorted(all_ops, key=lambda o: o.start_ns),
                   executions=sorted(executions, key=lambda o: o.start_ns))


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def idle_by_span(r: Reduced, k: int = 10) -> list[list]:
    """Idle seconds summed by the host span each gap fell in, largest first."""
    acc: dict[str, float] = collections.defaultdict(float)
    for sec, name in r.gaps:
        acc[name] += sec
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]
