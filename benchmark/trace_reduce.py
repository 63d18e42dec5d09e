"""Reduce a profiler trace of the measured window to the run's numbers.

Input is plain events, ``(name, start_ns, duration_ns)``, on the
profiler's one clock:

- device events: the ops that ran on each chip (the ``XLA Ops`` line of
  each ``/device:TPU:<n>`` plane; its ``XLA Modules`` line where a plane
  has no op line);
- host events: every span of every host thread (the benchmark's own
  ``benchmark.*`` spans, the runtime's ``PJRT_*`` calls, and so on);
- the window: the ``benchmark.window`` span.

Busy time is the union of a chip's op intervals inside the window,
averaged over the chips used; idle share is 1 minus busy over window.
The ten longest idle gaps (stretches of the window in which no op ran
on a chip) are each named after the host span that covers the gap's
middle and started last, that is the innermost thing the host was doing
then, or ``unattributed``.
"""

from __future__ import annotations

WINDOW_SPAN = "benchmark.window"
TOP = 10


def _union(intervals: list) -> list:
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def _clip(intervals: list, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_events(devices: dict, host: list, window: tuple) -> dict:
    """``devices``: chip name -> [(name, start_ns, dur_ns)]; ``host``:
    [(name, start_ns, dur_ns)]; ``window``: (start_ns, end_ns).  Returns
    ``busy_s``, ``window_s``, ``device_ops`` and ``idle_gaps`` (each a
    list of ``[name, seconds]``, longest first, at most ten)."""
    lo, hi = window
    if hi <= lo:
        raise ValueError(f"empty trace window {window}")
    busy_total, op_time, gaps = 0, {}, []
    for _chip, events in sorted(devices.items()):
        spans = _clip([(s, s + d) for _n, s, d in events], lo, hi)
        merged = _union(spans)
        busy_total += sum(e - s for s, e in merged)
        for name, s, d in events:
            inside = min(s + d, hi) - max(s, lo)
            if inside > 0:
                op_time[name] = op_time.get(name, 0) + inside
        edge = lo
        for s, e in merged + [[hi, hi]]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
    n_chips = max(1, len(devices))
    host_spans = [(s, s + d, name) for name, s, d in host
                  if name != WINDOW_SPAN and d > 0]
    named_gaps = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = (g0 + g1) // 2
        covering = [(s, name) for s, e, name in host_spans
                    if s <= mid < e]
        name = max(covering)[1] if covering else "unattributed"
        named_gaps.append([name, (g1 - g0) / 1e9])
    ops = sorted(([n, t / 1e9] for n, t in op_time.items()),
                 key=lambda kv: -kv[1])
    return {
        "busy_s": busy_total / n_chips / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": ops[:TOP],
        "idle_gaps": named_gaps[:TOP],
    }


def op_name(text: str) -> str:
    """An XLA op event's name is its whole HLO instruction
    (``%while.7 = (s32[]...) while(...)``): keep the instruction name."""
    return text.split(" = ", 1)[0].lstrip("%")


def profiler_options():
    """Host spans and runtime calls, without the Python tracer (which
    records every Python call and slows the host several times over)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return options


def events_from_xplane(path: str) -> tuple:
    """(devices, host, window) from an ``.xplane.pb`` file, read with
    nothing but JAX's own ``ProfileData``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, window = {}, [], None
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            line = lines.get("XLA Ops")
            if line is None:
                line = lines.get("XLA Modules")
            if line is not None:
                devices[plane.name] = [(op_name(e.name), int(e.start_ns),
                                        int(e.duration_ns))
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, int(e.start_ns), int(e.duration_ns))
                    if e.name == WINDOW_SPAN:
                        window = (ev[1], ev[1] + ev[2])
                    host.append(ev)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    return devices, host, window

