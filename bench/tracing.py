"""Reductions from a profiler trace to device metrics.

The trace is read with ``jax.profiler.ProfileData``.  A device's plane is
named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation
the device ran, named by its whole HLO text (fused Pallas launches are the
instructions ``_run_chain.<n>`` and ``_run_horizontal.<n>``, after the
program's jitted wrappers), and its ``XLA Modules`` line
one event per program execution.  Host threads are the lines of
``/host:CPU``.  Events are kept as plain ``(name, start_s, end_s)`` tuples so
the reductions below can be checked on hand-built lists; an op keeps its
instruction name and result type (``short_name``).
"""
from __future__ import annotations

import glob
import os
import re

KERNELS = {"chain": re.compile(r"^_run_chain(\.\d+)?( |$)"),
           "horizontal": re.compile(r"^_run_horizontal(\.\d+)?( |$)")}
# an op event is named by its whole HLO text: "%name = type{layout} op(...)"
HLO = re.compile(r"^%?([^\s=]+)(?: = ([^\s{]+))?")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def short_name(name: str) -> str:
    """An op's HLO instruction name and result type, without the layout and
    operands: ``_run_chain.26 s8[32,56,56,64]``."""
    m = HLO.match(name)
    if not m:
        return name
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def kernel_kind(name: str) -> str | None:
    for kind, pat in KERNELS.items():
        if pat.match(name):
            return kind
    return None


def read(trace_dir: str) -> dict:
    """{plane: {"ops": [...], "modules": [...]}} of every TPU in the newest
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    devices = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices[plane.name] = {
                "ops": [(short_name(n), a, b)
                        for n, a, b in lines.get(OPS_LINE, [])],
                "modules": lines.get(MODULES_LINE, [])}
    return devices


def _events(line) -> list:
    return [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in line.events]


def clip(events, t0: float, t1: float) -> list:
    """Events cut to the window [t0, t1]; those outside it dropped."""
    return [(n, max(a, t0), min(b, t1)) for n, a, b in events
            if b > t0 and a < t1]


def merge(events) -> list:
    """Union of the events' intervals as sorted disjoint (start, end)."""
    out = []
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(iv) for iv in out]


def busy_s(events) -> float:
    return sum(b - a for a, b in merge(events))


def gaps(events, t0: float, t1: float) -> list:
    """Idle intervals of [t0, t1] between the union of ``events``."""
    out, cur = [], t0
    for a, b in merge(clip(events, t0, t1)):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        out.append((cur, t1))
    return out


def outside_kernel_s(ops) -> float:
    """Busy time in which no fused launch ran: pads, slices, concats,
    softmax, copies."""
    kern = [e for e in ops if kernel_kind(e[0])]
    return busy_s(ops) - busy_s(kern)


def _with_launches(ops, modules) -> list:
    """[(module start, ops inside)] of every program execution that lies
    whole in the trace and ran at least one fused launch, in time order."""
    ops = sorted(ops, key=lambda e: e[1])
    out = []
    for _, a, b in sorted(modules, key=lambda m: m[1]):
        inside = [e for e in ops if e[1] >= a and e[2] <= b]
        if any(kernel_kind(e[0]) for e in inside):
            out.append((a, inside))
    return out


def complete_runs(ops, modules) -> list:
    """Per program execution that lies whole in the trace and ran at least
    one fused launch: the ops inside it."""
    return [inside for _, inside in _with_launches(ops, modules)]


def program_period_s(ops, modules) -> float | None:
    """Mean device time from one program execution's start to the next's,
    over the executions that ran fused launches; None under two."""
    starts = [a for a, _ in _with_launches(ops, modules)]
    if len(starts) < 2:
        return None
    return (starts[-1] - starts[0]) / (len(starts) - 1)


def kernel_time(runs, kind: str) -> float:
    return sum(b - a for run in runs for n, a, b in run
               if kernel_kind(n) == kind)


def top_ops(ops, n: int = 10) -> list:
    """[[name, seconds]] of the ``n`` operations that took most time."""
    tot: dict = {}
    for name, a, b in ops:
        tot[name] = tot.get(name, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def named_gaps(ops, modules, t0: float, t1: float, n: int = 10) -> list:
    """[[what the gap waited on, seconds]] of the ``n`` longest idle gaps.
    The chip's trace holds no host plane, so a gap is named from the
    device's side: inside a program it waits before its next op (an input
    copy, a weight copy); between programs the device waits on the host to
    form, stack, copy in and dispatch the next batch."""
    starts = sorted((a, name) for name, a, _ in ops)
    out = []
    for a, b in sorted(gaps(ops, t0, t1), key=lambda g: g[0] - g[1])[:n]:
        inside = any(ma <= a and b <= mb for _, ma, mb in modules)
        if inside:
            nxt = next((name for s0, name in starts if s0 >= b), "the end")
            out.append([f"in program, before {nxt}", b - a])
        else:
            out.append(["between programs: host forms, stacks, copies in "
                        "and dispatches the next batch", b - a])
    return out
