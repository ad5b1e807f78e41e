"""The profiler trace, reduced to what the per-layer readers use.

``capture`` records the window with JAX's profiler (host spans and device
operations; the Python tracer off). ``load`` reads the ``.xplane.pb`` into a
``Trace``: per TPU device the intervals of its ``XLA Ops`` line (an
operation ran) and of its ``XLA Modules`` line (a program ran), and the
benchmark's own ``bench.*`` host spans, all on the profiler's one clock in
nanoseconds. ``Trace`` also saves to and loads from a small JSON file, so
that a recorded chip trace can be checked in and the reductions tested on
it.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import shutil
from dataclasses import dataclass, field

import numpy as np

SPAN_PREFIX = "bench."


@dataclass
class Trace:
    window: tuple  # (start, end) ns of the traced window's span
    ops: list  # per device: (n, 2) float64 [start, end) ns of XLA Ops
    op_names: list  # per device: n names "<program>:<op>"
    modules: list  # per device: (m, 2) [start, end) ns of XLA Modules
    module_names: list  # per device: m program names
    spans: list = field(default_factory=list)  # (name, start, end, {stat: str})

    # -------------------------------------------------------- persistence
    def to_json(self) -> dict:
        return {"window": list(self.window),
                "ops": [o.tolist() for o in self.ops], "op_names": self.op_names,
                "modules": [m.tolist() for m in self.modules],
                "module_names": self.module_names,
                "spans": [list(s) for s in self.spans]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        arr = lambda x: np.asarray(x, np.float64).reshape(-1, 2)  # noqa: E731
        return cls(tuple(d["window"]), [arr(o) for o in d["ops"]], d["op_names"],
                   [arr(m) for m in d["modules"]], d["module_names"],
                   [tuple(s) for s in d["spans"]])

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def open(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))

    # ---------------------------------------------------------- selections
    def request_spans(self, kind: str | None = None) -> np.ndarray:
        """(r, 2) intervals of ``bench.request`` spans (of one ``kind``)."""
        out = [(s, e) for n, s, e, st in self.spans
               if n == "bench.request" and (kind is None or st.get("kind") == kind)]
        return np.asarray(out, np.float64).reshape(-1, 2)


# ------------------------------------------------------------ reductions
def merge(iv: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals as disjoint sorted intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    group = np.cumsum(new) - 1
    stops = np.zeros(len(starts))
    np.maximum.at(stops, group, iv[:, 1])
    return np.stack([starts, stops], 1)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1) if len(iv) else iv
    return iv[iv[:, 1] > iv[:, 0]] if len(iv) else np.zeros((0, 2))


def length(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two unions of intervals (each merged first)."""
    a, b = merge(a), merge(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, np.float64).reshape(-1, 2)


def busy_ns(tr: Trace, device: int = 0, within: np.ndarray | None = None) -> float:
    """Nanoseconds in which an operation ran on ``device`` inside the traced
    window, and inside the intervals ``within`` when given."""
    ops = clip(tr.ops[device], *tr.window)
    return length(merge(ops) if within is None else intersect(ops, within))


def mean_busy_s(tr: Trace) -> float:
    return float(np.mean([busy_ns(tr, d) for d in range(len(tr.ops))])) / 1e9


def window_s(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) / 1e9


def idle_share_pct(tr: Trace) -> float:
    return 100.0 * (1.0 - mean_busy_s(tr) / window_s(tr))


def programs_in(tr: Trace, iv: np.ndarray, device: int = 0) -> int:
    """Program executions on ``device`` that start inside the intervals."""
    starts = tr.modules[device][:, 0]
    iv = merge(iv)
    k = np.searchsorted(iv[:, 0], starts, side="right") - 1
    return int(((k >= 0) & (starts < iv[np.maximum(k, 0), 1])).sum())


def top_ops(tr: Trace, n: int = 10, device: int = 0) -> list:
    """The ``n`` device operations with the most time in the window."""
    tot: dict = {}
    for (s, e), name in zip(clip_rows(tr.ops[device], tr.window), tr.op_names[device]):
        tot[name] = tot.get(name, 0.0) + (e - s)
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda x: -x[1])[:n] if v > 0]


def clip_rows(iv: np.ndarray, window) -> np.ndarray:
    """Each interval clipped to the window, rows kept (zero length outside)."""
    lo, hi = window
    s = np.clip(iv[:, 0], lo, hi)
    return np.stack([s, np.clip(iv[:, 1], s, hi)], 1) if len(iv) else iv


def idle_gaps(tr: Trace, n: int = 10, device: int = 0) -> list:
    """The ``n`` longest idle gaps of the device in the window, each named by
    the innermost ``bench.*`` host span around its middle."""
    busy = merge(clip(tr.ops[device], *tr.window))
    edges = np.concatenate([[tr.window[0]], busy.ravel(), [tr.window[1]]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    longest = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")[:n]]
    spans = [(s, e, name) for name, s, e, _ in tr.spans if name != "bench.window"]
    out = []
    for s, e in longest:
        mid = (s + e) / 2
        inner = [x for x in spans if x[0] <= mid < x[1]]
        label = min(inner, key=lambda x: x[1] - x[0])[2] if inner else "outside requests"
        out.append([label, (e - s) / 1e9])
    return out


# ------------------------------------------------------------- capture
@contextlib.contextmanager
def capture(out_dir: str):
    """Profile the body into ``out_dir`` (emptied first); Python tracer off."""
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _program(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op(name: str) -> str:
    return name.split(" = ", 1)[0]


def load(out_dir: str) -> Trace:
    """The ``Trace`` of the one ``.xplane.pb`` under ``out_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {out_dir}, found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    ops, op_names, mods, mod_names, spans = [], [], [], [], []
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            m = lines.get("XLA Modules", [])
            mod_iv = np.asarray([(e.start_ns, e.start_ns + e.duration_ns) for e in m],
                                np.float64).reshape(-1, 2)
            mod_nm = [_program(e.name) for e in m]
            o = lines.get("XLA Ops", [])
            op_iv = np.asarray([(e.start_ns, e.start_ns + e.duration_ns) for e in o],
                               np.float64).reshape(-1, 2)
            # each op is named by the program it ran in
            k = np.searchsorted(mod_iv[:, 0], op_iv[:, 0], side="right") - 1 if len(m) else []
            op_nm = [f"{mod_nm[j] if j >= 0 else '?'}:{_op(e.name)}" for j, e in zip(k, o)]
            ops.append(op_iv)
            op_names.append(op_nm)
            mods.append(mod_iv)
            mod_names.append(mod_nm)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                      {k: str(v) for k, v in e.stats}))
    win = [(s, e) for n, s, e, _ in spans if n == "bench.window"]
    if not ops or not win:
        raise RuntimeError("the trace holds no TPU device plane or no bench.window span")
    spans.sort(key=lambda x: x[1])
    return Trace(win[0], ops, op_names, mods, mod_names, spans)
