#!/usr/bin/env python3
"""The controls: systems that break one stated guarantee, which ``correct``
must refuse. The benchmark's own runs never use them.

    python3 bench/control.py --workload <cell> --seeds 5,6,7 --seconds 10

runs the cell's whole run (set-up, window, check) once per seed in one
process with the cell's control in the program's place, on the chip, and
prints each run's compared numbers. ``correct`` must come out false on
every seed; the smallest reading of each number bounds its limit from
above (``PERF.md``).

``CONTROLS`` holds one control per store, so that a new configuration over
a store gets its control from its ``store`` key:

* ``readonly`` (float32 estimates): the plain reference answers in
  the program's place, exhaustively, with its estimator evaluated in
  bfloat16, the nearest precision below the configuration's.
* ``segmented`` (exact sketches): the program with its own
  lower-precision path switched on: every sealed segment distilled to
  N/2 bins (``SketchEngine.distill``) before the store is read.

``FAULTS`` are the faults the check must catch in any cell, planted in the
system under test: an answer altered where it is produced, half of a query
batch left unanswered, an insert acknowledged with the store left
unchanged, an update acknowledged with the store left unchanged, an update
that adds the new contents and leaves the old row live, and queries
answered from the contents before the updates acknowledged so far.
"""

from __future__ import annotations

import argparse
import json
import sys
import types

import numpy as np

if __package__ in (None, ""):
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import reference as ref  # noqa: E402
from bench import system as real  # noqa: E402


class _Wrap:
    """A system adaptor that defers to the real one unless overridden."""

    Builder = real.Builder
    PHASES = real.PHASES
    query = staticmethod(real.query)
    insert = staticmethod(real.insert)
    update = staticmethod(real.update)
    views = staticmethod(real.views)


# ------------------------------------------------------------- controls
class Bfloat16Reference(_Wrap):
    """The reference in the program's place, its estimate in bfloat16."""

    class Builder:
        def __init__(self, cfg, pi, backend=None):
            self.eng = types.SimpleNamespace(cfg=cfg, pi=pi, rows=[])

        def add(self, rows):
            self.eng.rows.append(np.asarray(rows))

        def finish(self):
            rows = np.concatenate(self.eng.rows)
            self.eng.n = len(rows)
            self.eng.chunks = ref.device_chunks(lambda lo, hi: rows[lo:hi], len(rows))
            return self.eng

    @staticmethod
    def query(eng, idx, k):
        cfg = eng.cfg
        s, ids, *_ = ref.scan_topk(
            np.asarray(idx), np.full(len(idx), eng.n), eng.chunks, kind="binsketch",
            universe=cfg["n_bins"], pi=eng.pi, n_bins=cfg["n_bins"], k=k, dtype="bfloat16")
        return s[:, :k], ids[:, :k]


class Distilled(_Wrap):
    """The program with every sealed segment distilled to N/2 bins."""

    @staticmethod
    def views(eng):
        eng.distill(widths=(eng.cfg.n_bins // 2,), background=False)
        return real.views(eng)


CONTROLS = {"readonly": Bfloat16Reference, "segmented": Distilled}


# --------------------------------------------------------------- faults
class AlteredAnswer(_Wrap):
    """The last slot of every answer replaced by another doc."""

    @staticmethod
    def query(eng, idx, k):
        s, i = real.query(eng, idx, k)
        i = i.copy()
        i[:, -1] = (i[:, 0] + 1 + int(i.max())) % max(int(eng.store.size), 1)
        return s, i


class HalfBatch(_Wrap):
    """Only the first half of each query batch answered."""

    @staticmethod
    def query(eng, idx, k):
        h = max(len(idx) // 2, 1)
        s, i = real.query(eng, idx[:h], k)
        pad = len(idx) - h
        return (np.concatenate([s, np.full((pad, k), -np.inf, s.dtype)]),
                np.concatenate([i, np.full((pad, k), -1, i.dtype)]))


class UnchangedState(_Wrap):
    """Inserts acknowledged under fresh ids, the store left as it was."""

    @staticmethod
    def insert(eng, idx):
        lo = getattr(eng, "_fake_next", None) or eng.store.next_id
        eng._fake_next = lo + len(idx)
        return lo, lo + len(idx), False


class UnchangedUpdate(_Wrap):
    """Updates acknowledged, the store left as it was."""

    @staticmethod
    def update(eng, ids, idx):
        return False


class UntombstonedUpdate(_Wrap):
    """Updates that store the new contents under the old ids and leave the
    old rows live."""

    @staticmethod
    def update(eng, ids, idx):
        import jax.numpy as jnp

        store = eng.store
        n_sealed = len(store.sealed)
        counts = store._count_rows(jnp.asarray(idx), eng.backend)
        store._insert_counts(counts, ids=np.asarray(ids, np.int64), now=0.0, exact=True)
        return len(store.sealed) > n_sealed


class StaleReads(_Wrap):
    """Updates acknowledged but held back until the store is checked, so
    that every query is answered from the contents before them."""

    @staticmethod
    def update(eng, ids, idx):
        eng.__dict__.setdefault("_held", []).append((ids, idx))
        return False

    @staticmethod
    def views(eng):
        for ids, idx in eng.__dict__.pop("_held", []):
            real.update(eng, ids, idx)
        return real.views(eng)


FAULTS = {"altered_answer": AlteredAnswer, "half_batch": HalfBatch,
          "unchanged_state": UnchangedState, "unchanged_update": UnchangedUpdate,
          "untombstoned_update": UntombstonedUpdate, "stale_reads": StaleReads}


def main(argv=None) -> int:
    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        spec = run.load_spec(run.ROOT, args.workload)
        sys.path.insert(1, str(run.ROOT / "src"))
        run.enable_cache(run.ROOT)  # before the program's first compile
        device = run.device_check(spec.cell["chips"])
        peaks = run.load_peaks(run.ROOT, device["kind"])
    except run.Refused as e:
        print(f"control: refused: {e}", file=sys.stderr)
        return e.code
    control = CONTROLS[spec.cfg["store"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(spec, seed, args.seconds, False, device, peaks, system=control)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"], "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
