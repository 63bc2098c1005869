"""Reduce a JAX profiler trace (``.xplane.pb``) of one window to numbers.

* Device busy time is the union of the intervals in which an operation
  ran on a device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane),
  clipped to the window and averaged over the devices that ran any.
* Program executions are the events of the ``XLA Modules`` line that
  start inside the window, counted by module name; ``executions`` counts
  those of the verify program alone (``VERIFY_MODULES``).
* ``flushes`` holds, for each ``serve.dispatch`` span that overlaps the
  window, the verify-program executions that started in the overlap, the
  device busy time in it, and the share of the span that lies in the
  window.  A flush's device work (its batch, the integrity guard's
  canaries, any bisection) runs inside its span, so executions and busy
  time over the summed shares are per-flush figures that a flush cut by
  an edge of the window does not bias.
* ``device_ops`` lists the HLO instructions that took the most device
  time;
  ``idle_gaps`` sums the device's idle time by what the host was doing:
  the innermost program span open at the middle of each gap, or
  ``no span`` when none was (waiting for requests or for a flush).

Host and device share the profiler's clock.  The harness opens the
window with a ``TraceAnnotation`` named ``MARKER`` and notes the
monotonic time at which it did; that pair aligns the program's spans
(monotonic seconds) with the trace (nanoseconds).
"""

from __future__ import annotations

MARKER = "benchmark.window_start"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
#: HLO module names of the verify program: ``jit__verify_kernel_h2c`` (or
#: ``jit__verify_kernel``) when compiled by tracing, ``jit_call_exported``
#: when installed from the AOT store, which holds verify programs only
VERIFY_MODULES = ("_verify_kernel", "call_exported")
#: host and device clocks agree to about a millisecond
CLOCK_SLACK_NS = 2e6


def is_verify(module: str) -> bool:
    return any(p in module for p in VERIFY_MODULES)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def op_name(text: str) -> str:
    """An HLO op event's instruction name (``%fusion.12``), without the
    rest of its HLO text."""
    return text.split(" = ", 1)[0]


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def marker_ns(profile) -> int | None:
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARKER:
                    return int(ev.start_ns)
    return None


def device_planes(profile) -> list:
    return [p for p in profile.planes if p.name.startswith("/device:TPU:")]


def reduce_profile(profile, marker_mono: float, start: float, end: float,
                   spans=()) -> dict:
    """Numbers of the window ``[start, end]`` (monotonic seconds) of a
    loaded profile whose marker was written at ``marker_mono``."""
    m_ns = marker_ns(profile)
    if m_ns is None:
        raise ValueError(f"no {MARKER!r} event in the trace")

    def ns(t: float) -> float:
        return m_ns + (t - marker_mono) * 1e9

    w0, w1 = ns(start), ns(end)
    busy_per_device = []
    op_time: dict = {}
    modules: dict = {}
    busy_all = []
    module_iv = []
    for plane in device_planes(profile):
        lines = {line.name: line for line in plane.lines}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if ops is None:
            continue
        intervals = []
        for ev in ops.events:
            s = float(ev.start_ns)
            e = s + float(ev.duration_ns)
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            intervals.append((s, e))
            key = op_name(ev.name)
            op_time[key] = op_time.get(key, 0.0) + (e - s) / 1e9
        mods = []
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                s = float(ev.start_ns)
                if w0 <= s < w1:
                    modules[ev.name] = modules.get(ev.name, 0) + 1
                    mods.append((s, s + float(ev.duration_ns), ev.name))
        if not intervals:
            continue
        union = _union(intervals)
        busy_per_device.append(sum(e - s for s, e in union) / 1e9)
        if not busy_all:
            busy_all = union
            module_iv = mods
    window_s = (w1 - w0) / 1e9
    busy_s = (sum(busy_per_device) / len(busy_per_device)
              if busy_per_device else 0.0)

    # idle gaps of the first busy device, labelled by the host's span
    gaps = []
    t = w0
    for s, e in busy_all:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < w1:
        gaps.append((t, w1))
    span_ns = [(ns(sp["start"]), ns(sp["end"]), sp["name"]) for sp in spans]

    flushes = []
    for s0, s1, name in span_ns:
        if name != "serve.dispatch" or s1 <= w0 or s0 >= w1 or s1 <= s0:
            continue
        a = max(s0, w0) - (CLOCK_SLACK_NS if s0 >= w0 else 0)
        b = min(s1, w1) + (CLOCK_SLACK_NS if s1 <= w1 else 0)
        calls = sum(1 for ms, _me, mname in module_iv
                    if a <= ms <= b and is_verify(mname))
        busy = sum(min(e, b) - max(s, a) for s, e in busy_all if e > a and s < b)
        share = (min(s1, w1) - max(s0, w0)) / (s1 - s0)
        flushes.append([calls, busy / 1e9, share])
    programs = _union([(s, e) for s, e, _n in module_iv])
    idle: dict = {}
    k = 0
    for s, e in gaps:
        mid = (s + e) / 2
        while k < len(programs) and programs[k][1] < mid:
            k += 1
        if k < len(programs) and programs[k][0] <= mid:
            label = "inside a program"
        else:
            open_ = [sp for sp in span_ns if sp[0] <= mid <= sp[1]]
            label = max(open_, key=lambda sp: sp[0])[2] if open_ else "no span"
        idle[label] = idle.get(label, 0.0) + (e - s) / 1e9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])][:TOP]

    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "devices": len(busy_per_device),
        "executions": sum(n for m, n in modules.items() if is_verify(m)),
        "modules": top(modules),
        "flushes": flushes,
        "device_ops": top(op_time),
        "idle_gaps": top(idle),
        "n_gaps": len(gaps),
    }


def reduce(path: str, marker_mono: float, start: float, end: float,
           spans=()) -> dict:
    return reduce_profile(load(path), marker_mono, start, end, spans)


def idle_share(trace: dict | None):
    """Percent of the window in which the device ran nothing, or None."""
    if not trace or trace["window_s"] <= 0 or trace["devices"] == 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def per_flush(trace: dict | None, column: int):
    """One column of ``flushes`` (0: verify executions, 1: busy seconds)
    summed over the window and divided by the flushes in it, each counted
    by its share inside; None where no verify execution ran in a flush."""
    rows = (trace or {}).get("flushes") or []
    if not rows or not sum(r[0] for r in rows):
        return None
    return sum(r[column] for r in rows) / sum(r[2] for r in rows)
