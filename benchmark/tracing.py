"""Profiler capture and the reduction from a trace to numbers.

The capture is JAX's own (``jax.profiler``), written with a Perfetto /
trace-viewer JSON copy that this module reads with ``gzip`` + ``json``.
In that JSON each device is a process (``/device:TPU:N``) whose threads
carry its events: ``XLA Ops`` (one event per device operation, the
source of busy time and kernel time) and ``XLA Modules`` (one event per
executable run, named after the jitted function). Host threads carry
the ``TraceAnnotation`` spans: the harness's ``bench/window`` and the
program's ``fdtd3d/<name>`` spans. The same JSON layout is recorded in
benchmark/tests/fixture.trace.json, on which tests check this reduction.
"""

import contextlib
import glob
import gzip
import json
import os
import re
import shutil

WINDOW = "bench/window"
_DEVICE_RE = re.compile(r"/device:(TPU|GPU):\d+")


@contextlib.contextmanager
def annotation(name):
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class Capture:
    def __init__(self, path):
        self.path = path

    def start(self):
        import jax
        shutil.rmtree(self.path, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # no per-function host events
        jax.profiler.start_trace(self.path, create_perfetto_trace=True,
                                 profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def load(self):
        files = glob.glob(os.path.join(self.path, "**",
                                       "perfetto_trace.json.gz"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"no trace written under {self.path}")
        return Trace.from_file(sorted(files)[-1])

    def keep(self, dest):
        files = glob.glob(os.path.join(self.path, "**",
                                       "perfetto_trace.json.gz"),
                          recursive=True)
        os.makedirs(os.path.dirname(os.path.abspath(dest)), exist_ok=True)
        shutil.copy(sorted(files)[-1], dest)

    def cleanup(self):
        shutil.rmtree(self.path, ignore_errors=True)


# ops that contain other ops on the same line (a scan's while loop):
# busy time takes the union anyway; the breakdown leaves them out
_CONTAINERS = ("while", "conditional", "call")


def _label(e):
    """What an op is, for the breakdown: the program's source line and
    the HLO category where the trace gives them, else the op name;
    None for a container op."""
    a = e.get("args") or {}
    cat = a.get("hlo_category", "op")
    if cat in _CONTAINERS:
        return None
    if cat == "custom-call":           # a kernel: its own name
        return re.sub(r"\.\d+$", "", e["name"]) + " (custom-call)"
    src = a.get("source", "")
    if src:
        parts = src.split("/")
        src = "/".join(parts[-2:])
        return f"{src} ({cat})"
    return e["name"]


def _union(intervals):
    """Total length of the union of (start, end) intervals."""
    total = 0.0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _clip(intervals, lo, hi):
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


class Trace:
    """Events of one capture, split by device and host; times in us."""

    def __init__(self, events):
        procs, threads = {}, {}
        for e in events:
            if e.get("ph") != "M":
                continue
            if e.get("name") == "process_name":
                procs[e["pid"]] = e["args"]["name"]
            elif e.get("name") == "thread_name":
                threads[(e["pid"], e.get("tid"))] = e["args"]["name"]
        self.ops = {}       # device -> [(ts, end, name, label, source)]
        self.modules = {}   # device -> [(ts, end, name)]
        self.host = []      # [(ts, end, name)]
        for e in events:
            if e.get("ph") != "X":
                continue
            ts = float(e["ts"])
            end = ts + float(e.get("dur", 0.0))
            pname = procs.get(e["pid"], "")
            tname = threads.get((e["pid"], e.get("tid")), "")
            if _DEVICE_RE.search(pname):
                if tname == "XLA Ops":
                    self.ops.setdefault(pname, []).append(
                        (ts, end, e["name"], _label(e),
                         (e.get("args") or {}).get("source", "")))
                elif tname == "XLA Modules":
                    self.modules.setdefault(pname, []).append(
                        (ts, end, e["name"]))
            else:
                self.host.append((ts, end, e["name"]))
        win = [(s, e) for s, e, n in self.host if n == WINDOW]
        if not win:
            raise RuntimeError(f"the trace has no {WINDOW!r} span")
        self.win = win[0]

    @classmethod
    def from_file(cls, path):
        op = gzip.open if path.endswith(".gz") else open
        with op(path, "rt") as f:
            d = json.load(f)
        return cls(d["traceEvents"] if isinstance(d, dict) else d)

    # -- windowed views ---------------------------------------------------

    def window_s(self):
        return (self.win[1] - self.win[0]) / 1e6

    def devices(self):
        return sorted(self.ops)

    def device_ops(self, dev):
        lo, hi = self.win
        return [o for o in self.ops.get(dev, []) if o[1] > lo and o[0] < hi]

    @staticmethod
    def union_s(intervals):
        return _union(intervals) / 1e6

    def busy_s(self):
        """Seconds in which an operation ran, averaged over devices."""
        devs = self.devices()
        if not devs:
            return 0.0
        lo, hi = self.win
        tot = 0.0
        for d in devs:
            tot += _union(_clip([(o[0], o[1]) for o in self.ops[d]], lo, hi))
        return tot / len(devs) / 1e6

    def module_runs(self, dev, name=None):
        """Executable runs in the window, of one executable if named."""
        lo, hi = self.win
        return [m for m in self.modules.get(dev, [])
                if m[1] > lo and m[0] < hi and (name is None or m[2] == name)]

    def busiest_module(self, dev):
        """The executable with the most device time in the window."""
        tot = {}
        for s, e, n in self.module_runs(dev):
            tot[n] = tot.get(n, 0.0) + e - s
        return max(tot, key=tot.get) if tot else None

    def pace_device(self, keep=None):
        """The device whose chunk program has the most device time in
        the window: over several devices it sets the pace. The chunk
        program is each device's busiest executable, or the executables
        whose name ``keep(name)`` accepts. With one device, that
        device; the first on a tie; None with none."""
        best, most = None, -1.0
        for d in self.devices():
            if keep is None:
                busiest = self.busiest_module(d)
                runs = self.module_runs(d, busiest) if busiest else []
            else:
                runs = [r for r in self.module_runs(d) if keep(r[2])]
            s = sum(e - b for b, e, _ in runs)
            if s > most:
                best, most = d, s
        return best

    def ops_where(self, dev, keep):
        """(start, end) of the window's device ops for which
        ``keep(name, source)`` holds, clipped to the window."""
        lo, hi = self.win
        return _clip([(o[0], o[1]) for o in self.ops.get(dev, [])
                      if keep(o[2], o[4])], lo, hi)

    def ops_within(self, dev, runs):
        """Device ops that lie inside the given module runs."""
        runs = sorted((r[0], r[1]) for r in runs)
        out = []
        i = 0
        for op in sorted(self.ops.get(dev, [])):
            while i < len(runs) and runs[i][1] < op[0]:
                i += 1
            if i < len(runs) and runs[i][0] <= op[0] and op[1] <= runs[i][1]:
                out.append(op)
        return out

    def host_spans(self, name):
        lo, hi = self.win
        return [(s, e) for s, e, n in self.host
                if n == name and e > lo and s < hi]

    def idle_gaps(self, dev):
        """Device-idle intervals inside the window, longest first."""
        lo, hi = self.win
        busy = sorted(_clip([(o[0], o[1]) for o in self.ops.get(dev, [])],
                            lo, hi))
        gaps = []
        cur = lo
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        return sorted(gaps, key=lambda g: g[0] - g[1])

    def host_doing(self, t):
        """The innermost of the program's ``fdtd3d/`` host spans that
        covers time t."""
        best = None
        for s, e, n in self.host:
            if not n.startswith("fdtd3d/") or not (s <= t <= e):
                continue
            if best is None or e - s < best[1] - best[0]:
                best = (s, e, n)
        return best[2] if best else "host: outside the program's spans"

    def breakdown(self):
        """Top device ops by time and the longest idle gaps of the
        device that sets the pace, each with at most 10 entries,
        seconds unrounded."""
        dev = self.pace_device()
        if dev is None:
            return {"device_ops": [], "idle_gaps": []}
        tot = {}
        for s, e, _, label, _ in self.device_ops(dev):
            if label is not None:
                tot[label] = tot.get(label, 0.0) + (e - s) / 1e6
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
        gaps = [[self.host_doing(0.5 * (s + e)), (e - s) / 1e6]
                for s, e in self.idle_gaps(dev)[:10]]
        return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": gaps}
