"""Device time charged to the program's own spans.

The program under test opens ``repro.*`` profiler spans at its layer
boundaries (``repro.engine.query`` around ``repro.query.kernel_score``,
``repro.store.add`` around ``repro.store.count`` and
``repro.store.head_write``, ...). A device program runs after the host call
that launched it has returned, so its time does not fall inside the span
that launched it. ``load`` ties each program on the device (an ``XLA
Modules`` event) to the host call that launched it and records the names of
the ``repro.*`` spans open on the calling thread at that moment: the
program's stack. ``busy_ns_under`` then charges a span with the device
operations of every program launched under it.

The link is the profile's own. On a TPU the program carries a flow consumer
stat (``_c``) whose producer (``_p``) is the runtime's enqueue of it; that
enqueue, or an event enclosing it on its thread, consumes a flow from the
runtime's execute call, which consumes one from the launch call on the
Python thread, where the spans are. An enqueue the runtime deferred to a
task thread is followed back the same way. On the CPU the launch on the
Python thread carries the program's ``run_id``.

``load`` reads what ``tracefile.load`` reads, adds the ``repro.*`` spans to
``Trace.spans`` (so that ``tracefile.idle_gaps`` names a gap by the
innermost span of either kind) and keeps the stacks in
``ProgramTrace.stacks``, which a saved trace carries. A trace of a program
without these spans, or one saved without stacks, has only empty stacks, and
the readers built on this module return None there.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

from bench import tracefile
from bench.tracefile import Trace, clip, intersect, length, merge

PROGRAM_PREFIX = "repro."
#: the stat an execution's host events and its device program share
LINK_STAT = "run_id"


@dataclass
class ProgramTrace(Trace):
    # per device, per program (``modules`` order): the names of the
    # ``repro.*`` spans open where it was launched, outermost first
    stacks: list = field(default_factory=list)

    def to_json(self) -> dict:
        d = super().to_json()
        d["stacks"] = [[list(s) for s in dev] for dev in self.stacks]
        return d

    @classmethod
    def from_json(cls, d: dict) -> "ProgramTrace":
        base = Trace.from_json(d)
        stacks = [[tuple(s) for s in dev] for dev in d.get("stacks", [])]
        return cls(**vars(base), stacks=stacks or [[()] * len(m) for m in base.modules])


def has_program_spans(tr: Trace) -> bool:
    """Whether some device program was launched under a ``repro.*`` span."""
    return any(st for dev in getattr(tr, "stacks", ()) for st in dev)


def stacks_at(spans, launches) -> list:
    """The span stack of each program.

    ``spans``: (name, start, end, thread) of the program's spans;
    ``launches``: per program, (thread, time) of its launch, or None. A
    program's stack holds the spans open on its launching thread at the
    launch, outermost first."""
    by_thread: dict = {}
    for name, s, e, thread in spans:
        by_thread.setdefault(thread, []).append((s, -e, name))
    for v in by_thread.values():
        v.sort()
    out = []
    for launch in launches:
        thread, t = launch if launch is not None else (None, None)
        out.append(tuple(name for s, neg_e, name in by_thread.get(thread, ())
                         if s <= t < -neg_e))
    return out


class _HostEvents:
    """A profile's host events, indexed by their flow producer and
    ``run_id`` stats, with each event's enclosing event on its thread."""

    def __init__(self):
        self.events = []  # (thread, start, end, stats)
        self.parent = []  # index of the enclosing event on the thread, or -1
        self.producer = {}  # (flow type, id) -> event index
        self.first_run = {}  # run_id -> index of its earliest event

    def add_thread(self, thread, events) -> None:
        """``events``: (start, end, stats) of one thread's events, all of
        them, so that nesting is seen whole."""
        open_ = []
        for start, end, st in sorted(events, key=lambda x: (x[0], -x[1])):
            while open_ and open_[-1][1] <= start:
                open_.pop()
            i = len(self.events)
            self.events.append((thread, start, end, st))
            self.parent.append(open_[-1][0] if open_ else -1)
            open_.append((i, end))
            if "_p" in st:
                self.producer[(st.get("_pt"), st["_p"])] = i
            if LINK_STAT in st:
                run = st[LINK_STAT]
                if run not in self.first_run or start < self.events[self.first_run[run]][1]:
                    self.first_run[run] = i

    def launch(self, st: dict):
        """(thread, time) where the program with device-side stats ``st``
        was launched: its host producer (or, without one, the earliest host
        event of its ``run_id``), followed back through flow links to the
        calling thread. A flow link is a consumer stat (``_c``) on an event
        or on one enclosing it, whose producer (``_p``) is known."""
        i = self.producer.get((st.get("_ct"), st["_c"])) if "_c" in st else None
        if i is None:
            i = self.first_run.get(st.get(LINK_STAT))
        seen = set()
        while i is not None and i not in seen:
            seen.add(i)
            nxt, node = None, i
            while node >= 0 and nxt is None:
                c = self.events[node][3]
                if "_c" in c:
                    nxt = self.producer.get((c.get("_ct"), c["_c"]))
                node = self.parent[node]
            if nxt is None:
                thread, start = self.events[i][:2]
                return thread, start
            i = nxt
        return None


def load(out_dir: str) -> ProgramTrace:
    """``tracefile.load`` of ``out_dir``, with the program's spans and the
    stack of each device program."""
    from jax.profiler import ProfileData

    base = tracefile.load(out_dir)
    (path,) = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    spans, kept, host, modules = [], [], _HostEvents(), []
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            mods = next((list(ln.events) for ln in plane.lines if ln.name == "XLA Modules"),
                        [])
            modules.append([{k: v for k, v in e.stats} for e in mods])
        elif plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                thread, events = (plane.name, i), []
                for e in ln.events:
                    st = {k: v for k, v in e.stats}
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith(PROGRAM_PREFIX):
                        spans.append((e.name, e.start_ns, end, thread))
                        kept.append((e.name, e.start_ns, end,
                                     {k: str(v) for k, v in st.items()}))
                    events.append((e.start_ns, end, st))
                host.add_thread(thread, events)
    stacks = [stacks_at(spans, [host.launch(st) for st in dev]) for dev in modules]
    fields = dict(vars(base), spans=sorted(base.spans + kept, key=lambda x: x[1]))
    return ProgramTrace(**fields, stacks=stacks)


# ------------------------------------------------------------ reductions
def _charged(tr: Trace, device: int, span_name: str | None) -> np.ndarray:
    """Per operation of ``device``: whether its program's stack holds
    ``span_name`` (any span, for None)."""
    mods, ops = tr.modules[device], tr.ops[device]
    stacks = tr.stacks[device] if getattr(tr, "stacks", None) else [()] * len(mods)
    hit = np.asarray([bool(st) if span_name is None else span_name in st for st in stacks],
                     bool)
    if not len(mods) or not len(ops):
        return np.zeros(len(ops), bool)
    k = np.searchsorted(mods[:, 0], ops[:, 0], side="right") - 1
    return (k >= 0) & hit[np.maximum(k, 0)]


def busy_ns_under(tr: Trace, span_name: str | None, within: np.ndarray | None = None,
                  device: int = 0, op: str | None = None) -> float:
    """Nanoseconds in which an operation of a program launched under
    ``span_name`` ran on ``device``, in the traced window (and inside
    ``within`` when given): the union of the operations' intervals, since
    operations nest. ``op`` keeps only operations whose name holds it;
    ``span_name`` None takes every program launched under any span."""
    sel = _charged(tr, device, span_name)
    if op is not None:
        sel &= np.asarray([op in n.split(":", 1)[-1] for n in tr.op_names[device]], bool)
    ops = clip(tr.ops[device][sel], *tr.window)
    return length(merge(ops) if within is None else intersect(ops, within))


def charged_share_pct(tr: Trace, within: np.ndarray, device: int = 0) -> float | None:
    """Of the device's busy time inside ``within``, the share, in %, of
    programs launched under some ``repro.*`` span."""
    busy = tracefile.busy_ns(tr, device, within=within)
    return 100.0 * busy_ns_under(tr, None, within, device) / busy if busy > 0 else None


def program_idle_ns(tr: Trace, within: np.ndarray, device: int = 0) -> float:
    """Nanoseconds inside ``within`` and inside some ``repro.*`` span in
    which no operation ran on ``device``."""
    prog = np.asarray([(s, e) for n, s, e, _ in tr.spans if n.startswith(PROGRAM_PREFIX)],
                      np.float64).reshape(-1, 2)
    inside = intersect(clip(prog, *tr.window), within)
    return length(inside) - length(intersect(clip(tr.ops[device], *tr.window), inside))


def device_us_per_insert_doc(ctx, span_name: str) -> float | None:
    """Device time of the programs launched under ``span_name`` inside
    insert requests, per document those requests inserted, in us."""
    tr = ctx.trace
    if tr is None or not has_program_spans(tr):
        return None
    spans = tr.request_spans("insert")
    docs = sum(r["docs"] for r in ctx.traced if r["op"] == "insert")
    if not len(spans) or docs == 0:
        return None
    return busy_ns_under(tr, span_name, within=spans) / 1e3 / docs


def program_idle_ms_per_request(ctx, kind: str) -> float | None:
    """Device idle time inside ``kind`` requests that falls inside a
    ``repro.*`` span, per request, in ms; notes the share of the requests'
    device time charged to the program's spans."""
    tr = ctx.trace
    if tr is None or not has_program_spans(tr):
        return None
    spans = tr.request_spans(kind)
    if not len(spans):
        return None
    share = charged_share_pct(tr, spans)
    if share is not None:
        ctx.note(f"program_idle_ms: {share:.3f} % of the device's busy time inside "
                 f"{kind} requests ran programs launched under a {PROGRAM_PREFIX}* span")
    return program_idle_ns(tr, spans) / 1e6 / len(spans)
