"""From a profiler trace of the window to the per-layer metrics.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote: the ops
each TPU ran (the "XLA Ops" line of each ``/device:TPU:<n>`` plane, with
the XLA module, i.e. the jitted program, each belongs to) and the host
spans of the benchmark (names starting with ``bench.``) and of the
program (``fft.``, ``repro.spans``). A ``Trace`` reduces them, always
within the window span:

  busy       the union of the intervals in which any op ran on a device;
  idle       one minus busy over the window, averaged over the devices;
  gaps       the idle intervals, each split by the innermost host span,
             the benchmark's or the program's, that the host was in;
  exposed    the time in which a collective ran on a device and no other
             op of the same program did, over that program's busy time.
"""

from __future__ import annotations

import bisect
import heapq
import re
from collections import defaultdict
from dataclasses import dataclass, field

from bench import intervals, work
from bench.harness import WINDOW_SPAN

#: host spans the benchmark writes; the window is ``bench.window``
SPAN_PREFIX = "bench."
#: host spans the program writes (``repro.spans``)
PROGRAM_PREFIX = "fft."
#: HLO opcodes that move data between chips (``-start``/``-done`` halves
#: of the asynchronous ones count as the opcode)
COLLECTIVE_OPCODES = frozenset({
    "all-to-all", "ragged-all-to-all", "all-gather", "all-reduce",
    "reduce-scatter", "collective-permute", "collective-broadcast", "send",
    "recv"})
#: the opcode of an HLO instruction's text: the first word, after the
#: ``=`` and the shape, that opens its operand list
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
#: names of collective instructions, ``-`` or ``_`` between the words,
#: for ops whose text carries no opcode
_COLLECTIVE_NAME = re.compile(
    r"^(ragged[-_])?(all[-_]to[-_]all|all[-_]gather|all[-_]reduce|"
    r"reduce[-_]scatter|collective[-_]permute|collective[-_]broadcast|"
    r"send|recv)([-_.]|$)")


def opcode(hlo_text: str) -> str:
    """``%all_to_all.48 = f32[..]{..} all-to-all(..), ..`` ->
    ``all-to-all``; "" where the text is not an HLO instruction."""
    _, sep, rhs = hlo_text.partition(" = ")
    m = _OPCODE.search(" " + rhs) if sep else None
    return m.group(1) if m else ""


@dataclass(frozen=True)
class Op:
    name: str
    start: float
    end: float
    module: str       # the jitted program (XLA module) the op ran in
    opcode: str = ""  # the HLO opcode, where the trace gives it

    @property
    def collective(self) -> bool:
        """By the opcode, or where there is none by the name, whichever
        way its words are joined (``all-to-all.2``, ``all_to_all.48``)."""
        if self.opcode:
            base = re.sub(r"-(start|done|update)$", "", self.opcode)
            return base in COLLECTIVE_OPCODES
        return bool(_COLLECTIVE_NAME.search(self.name))


@dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    devices: dict            # device id -> [Op]
    spans: list              # [Span], the benchmark's and program's
    window: tuple            # (start, end) seconds
    runs: dict = field(default_factory=dict)  # device -> [(s, e, module)]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _clip(self, ops) -> list:
        return intervals.clip(((o.start, o.end) for o in ops), *self.window)

    def busy_s(self, dev) -> float:
        return intervals.length(self._clip(self.devices[dev]))

    def busy_mean_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_mean_s() / self.window_s)

    def module_ops(self, prefix: str) -> dict:
        """Each device's ops of the programs whose name starts with
        ``prefix`` (empty lists where none ran)."""
        return {d: [o for o in ops if o.module.startswith(prefix)]
                for d, ops in self.devices.items()}

    def module_runs(self, prefix: str) -> float:
        """Runs of the programs whose name starts with ``prefix``, each
        counted by the share of it inside the window, averaged over the
        devices."""
        lo, hi = self.window
        return sum(sum((min(e, hi) - max(s, lo)) / (e - s)
                       for s, e, m in runs
                       if m.startswith(prefix) and e > lo and s < hi)
                   for runs in self.runs.values()) / max(len(self.runs), 1)

    def op_seconds(self, ops_by_dev: dict) -> float:
        """Busy seconds of the given ops, averaged over the devices."""
        return sum(intervals.length(self._clip(ops))
                   for ops in ops_by_dev.values()) / len(ops_by_dev)

    def exposed_collective_pct(self, prefix: str):
        """Share of a program's busy time in which only collectives ran,
        averaged over the devices; None where the program ran no
        collective."""
        shares = []
        for ops in self.module_ops(prefix).values():
            coll = [o for o in ops if o.collective]
            busy = intervals.length(self._clip(ops))
            if not coll or not busy:
                continue
            alone = intervals.subtract(
                self._clip(coll), self._clip(o for o in ops
                                             if not o.collective))
            shares.append(100.0 * intervals.length(alone) / busy)
        return sum(shares) / len(shares) if shares else None

    def idle_gaps(self, top: int = 10, dev=None) -> list:
        """The longest idle pieces of one device (the first by default),
        each named by the innermost host span it fell in: the shortest
        span, of any thread, that covers it."""
        dev = min(self.devices) if dev is None else dev
        spans = sorted(self.spans, key=lambda s: s.start)
        bounds = sorted({t for s in spans for t in (s.start, s.end)})
        # one sweep in time: the spans begun so far, shortest on top; a
        # span that has ended leaves once it reaches the top
        heap, k, pieces = [], 0, []
        for a, b in intervals.gaps(self._clip(self.devices[dev]),
                                   *self.window):
            cuts = [a, *bounds[bisect.bisect_right(bounds, a):
                               bisect.bisect_left(bounds, b)], b]
            for lo, hi in zip(cuts, cuts[1:]):
                while k < len(spans) and spans[k].start <= lo:
                    s = spans[k]
                    heapq.heappush(heap, (s.end - s.start, k, s))
                    k += 1
                while heap and heap[0][2].end <= lo:
                    heapq.heappop(heap)
                name = heap[0][2].name if heap else "no host span"
                if pieces and pieces[-1][0] == name and pieces[-1][2] == lo:
                    pieces[-1][1] += hi - lo
                    pieces[-1][2] = hi
                else:
                    pieces.append([name, hi - lo, hi])
        pieces.sort(key=lambda p: -p[1])
        return [[n, s] for n, s, _ in pieces[:top]]

    def device_ops(self, top: int = 10) -> list:
        """Ops by total device seconds in the window, averaged over the
        devices, named ``<module>:<op>``."""
        total = defaultdict(float)
        for ops in self.devices.values():
            for o in ops:
                s, e = max(o.start, self.window[0]), min(o.end, self.window[1])
                if e > s:
                    total[f"{o.module}:{o.name}"] += e - s
        n = len(self.devices)
        rows = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n] for k, v in rows]


@dataclass
class Context:
    """What a per-layer metric's reader gets: the reduced trace (None when
    the run was not traced), the generator's own readings (``layer``) and the
    chip's kind for its peaks."""
    trace: Trace | None
    layer: dict
    device_kind: str

    @property
    def program_spans(self) -> list | None:
        """The program's host spans in the traced run (``fft.*``); None
        when the run was not traced."""
        if self.trace is None:
            return None
        return [s for s in self.trace.spans
                if s.name.startswith(PROGRAM_PREFIX)]

    @property
    def busy_s(self) -> float:
        return self.trace.busy_mean_s()

    @property
    def window_s(self) -> float:
        return self.trace.window_s

    def breakdown(self) -> dict:
        return {"device_ops": self.trace.device_ops(),
                "idle_gaps": self.trace.idle_gaps()}

    def roofline_pct(self, step: dict):
        """Least time of the step's nominal work over the device time of
        every op of the step's program; None where it did not run."""
        ops = self.trace.module_ops(step["module"])
        if not any(ops.values()):
            return None
        runs = self.trace.module_runs(step["module"])
        least, _ = work.least_seconds(step["ops"], step["bytes"],
                                      self.device_kind)
        return 100.0 * runs * least / self.trace.op_seconds(ops)


def _op_name(hlo_text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def _device_ops(plane) -> list:
    """The plane's ops, each tagged with the module run that holds it."""
    lines = {line.name: list(line.events) for line in plane.lines}
    runs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                   e.name.split("(", 1)[0])
                  for e in lines.get("XLA Modules", []))
    evs = sorted(lines.get("XLA Ops", []) + lines.get("Async XLA Ops", []),
                 key=lambda e: e.start_ns)
    ops, i = [], 0
    for e in evs:
        while i < len(runs) and runs[i][1] <= e.start_ns:
            i += 1
        module = (runs[i][2] if i < len(runs) and runs[i][0] <= e.start_ns
                  else "")
        ops.append(Op(_op_name(e.name), e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9, module,
                      opcode(e.name)))
    return ops, [(s * 1e-9, e * 1e-9, m) for s, e, m in runs]


def host_spans(pd) -> list:
    """The benchmark's and the program's host spans of a read trace
    (``jax.profiler.ProfileData``)."""
    return [Span(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX))]


def load(path: str) -> Trace:
    """Read a ``.xplane.pb``: TPU ops, module runs and host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, runs = {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            devices[dev], runs[dev] = _device_ops(plane)
    spans = host_spans(pd)
    window = [s for s in spans if s.name == WINDOW_SPAN]
    if not devices or len(window) != 1:
        raise ValueError(f"{path}: {len(devices)} TPU planes and "
                         f"{len(window)} {WINDOW_SPAN!r} spans")
    return Trace(devices=devices, spans=spans,
                 window=(window[0].start, window[0].end), runs=runs)


def context(outcome, device_kind: str) -> Context:
    trace = load(outcome.trace_file) if outcome.trace_file else None
    return Context(trace=trace, layer=outcome.layer, device_kind=device_kind)
