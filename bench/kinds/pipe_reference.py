"""Plain reference of the pipelined driver's step: a depth-2 pipeline whose
late workers fold back in with staleness weights.

Nothing here imports the program.  Given one chunk of steps, each step's
worker delays and the cut the driver waited for:

* the cut: workers slower than the ``wait``-th fastest are late; a late
  worker lands ``ceil((d − cutoff) / cutoff)`` steps later (its lag), and
  within the step's fold window its products still count;
* step t's workers compute at the iterate the pipeline has broadcast by
  then, ``θ_{t−1}`` (the chunk's first iterate for its first two steps);
  the master peels the cut for ``rounds`` rounds and updates the resolved
  coordinates with ``Mθ_w − b``;
* a step whose late workers land inside its window is kept: when some of
  them land ``lag`` steps later, the survivors of the source step are
  peeled again with only the workers still missing erased, and the
  coordinates newly resolved enter that later step's update weighted
  ``decay^lag`` (coordinates the source step already applied never count
  twice).

Values come from the uncoded computation in float32 (:mod:`reference`):
``precision`` ``"highest"`` or ``"bf16"`` (the control).
"""
from __future__ import annotations

import numpy as np

from bench.kinds import reference

__all__ = ["cuts", "FoldPlan", "plan_chunk"]


def cuts(delays: np.ndarray, wait: np.ndarray) -> tuple:
    """``(cut (T, W) bool, lags (T, W) int)`` of a chunk's delays at the
    cut ``wait (T,)`` workers."""
    d = np.asarray(delays)
    cutoff = np.sort(d, axis=1)[np.arange(d.shape[0]), np.asarray(wait) - 1]
    cut = d > cutoff[:, None]
    late = np.maximum(d.astype(np.float64) - cutoff[:, None], 0.0)
    lags = np.ceil(late / np.maximum(cutoff[:, None], 1e-30)).astype(int)
    return cut, lags


class FoldPlan:
    """Which coordinates each step of a chunk updates: ``cut[t]`` (W,) the
    workers its own decode erases, ``main[t]`` (k,) the coordinates that
    decode resolves; ``folds[t]`` a list of ``(source s, lag, newly (k,),
    remaining workers (W,))``; ``unresolved[t]`` the step's unresolved
    count once its late workers have landed."""

    def __init__(self, cut, main, folds, unresolved):
        self.cut, self.main, self.folds = cut, main, folds
        self.unresolved = unresolved


def plan_chunk(cut, lags, windows, h_cols, k: int, rows_per_worker: int,
               rounds: int, fold_rounds: int) -> FoldPlan:
    """The chunk's fold plan from its cuts, lags and fold windows, by the
    reference's own peeling."""
    def left_of(workers, n_rounds):
        erased = np.repeat(workers, rows_per_worker)
        return reference.peel_erased(h_cols, erased, n_rounds)[:k]

    T = len(cut)
    u: dict = {}
    live: list = []
    main, folds = [], [[] for _ in range(T)]
    late = np.zeros(T, int)
    for t in range(T):
        still = []
        for s in live:
            lag = t - s
            arriving = cut[s] & (lags[s] == lag)
            if arriving.any():
                remaining = cut[s] & (lags[s] > lag)
                left = left_of(remaining, fold_rounds)
                newly = u[s] & ~left
                folds[t].append((s, lag, newly, remaining))
                late[s] += int(newly.sum())
                u[s] = u[s] & left
            if lag < windows[s] and (cut[s] & (lags[s] > lag)).any():
                still.append(s)
        live = still
        left = left_of(cut[t], rounds)
        main.append(~left)
        if windows[t] > 0 and (cut[t] & (lags[t] > 0)
                               & (lags[t] <= windows[t])).any():
            live.append(t)
            u[t] = left.copy()
    unresolved = np.array([int((~m).sum()) for m in main]) - late
    return FoldPlan(cut, main, folds, unresolved)
