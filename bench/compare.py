"""The comparison that decides ``correct``: a served answer against the
reference's.

Two numbers per answer, each later held to its limit:

* ``rel_err`` - the largest relative gap of any float value: an aggregate,
  or, in a top-k answer, the rank key of the row the program put at
  position i against the reference's i-th (so a near-tie may swap places,
  and nothing else may);
* ``wrong`` - values that must match exactly and do not: integer fields,
  rows the reference does not qualify, missing or repeated rows, a wrong
  shape.
"""

from __future__ import annotations

import numpy as np

from reference import TOPK


def _gap(got: float, want: float) -> float:
    got, want = float(got), float(want)
    if got == want:
        return 0.0
    if not np.isfinite(got):
        return float("inf")
    return abs(got - want) / abs(want) if want else abs(got)


class Verdict:
    """Running ``rel_err`` (max) and ``wrong`` (sum) over many answers."""

    def __init__(self):
        self.rel_err = 0.0
        self.wrong = 0
        self.answers = 0

    def value(self, got, want) -> None:
        """One value: exact when the reference holds an integer."""
        if isinstance(want, (int, np.integer)):
            ok = np.isfinite(float(got)) and float(got) == float(want)
            self.wrong += 0 if ok else 1
        else:
            self.rel_err = max(self.rel_err, _gap(got, want))

    def answer(self, name: str, got, want) -> None:
        self.answers += 1
        if name in TOPK:
            self._topk(TOPK[name], got, want)
        elif isinstance(want, dict):
            for field, w in want.items():
                g = np.asarray(got[field]) if field in got else None
                if g is None or g.shape != w.shape:
                    self.wrong += w.size
                    continue
                for gv, wv in zip(g.tolist(), w.tolist()):
                    self.value(gv, wv)
        else:
            self.value(np.asarray(got).item(), want)

    def _topk(self, spec: dict, got: dict, want: dict) -> None:
        key, by = spec["key"], spec["by"]
        n = min(spec["k"], len(want[key]))
        if any(f not in got for f in want):
            self.wrong += n
            return
        cols = {f: np.asarray(got[f]).reshape(-1) for f in want}
        g = len(cols[key])
        if any(len(c) != g for c in cols.values()):
            self.wrong += n
            return
        self.wrong += abs(g - n)
        row_of = {int(k): j for j, k in enumerate(want[key].tolist())}
        seen = set()
        for i in range(min(g, n)):
            k = int(cols[key][i])
            j = row_of.get(k)
            if j is None or k in seen:
                self.wrong += 1
                continue
            seen.add(k)
            self.value(want[by][j].item(), want[by][i].item())
            for f, w in want.items():
                self.value(cols[f][i].item(), w[j].item())
