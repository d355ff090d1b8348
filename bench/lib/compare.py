"""The comparison that decides `correct`, and recall@10.

For every completed request the reference gives the exact filtered top-k
and the exact distance of each id the program returned. The numbers
compared, each against the cell's limit (`bench/limits/<cell>.json`),
cover every completed submission of the window, replays included:

  bad_ids           returned ids that are out of range, repeated, or fail
                    their request's filter (limit 0: an exact guarantee)
  unfinished        requests due in the window (a closed loop: every
                    submission, and every request of its recall set) that
                    never completed, a minute past its close (limit 0)
  dist_err_max      largest |returned distance - exact distance| over the
                    exact distance (floored at 1e-3), over all returned ids
  recall_loss       1 - mean recall@k over all completed requests

The end-to-end reading `recall_at_10` (`bench/lib/harness.py`) covers,
in a closed loop, a fixed set instead: its first `recall_requests`
submissions, the same requests for every seed and at every rate, where
one that never completed counts as recall 0. In an open loop it is the
mean over the completed requests due in the window.

Recall counts a returned id as a hit when it passes its filter and its
exact distance is within rounding of the reference's k-th distance, so a
tie at the k-th place is no miss. The larger a number, the worse.
"""
from __future__ import annotations

import numpy as np

DIST_FLOOR = 1e-3
TIE_REL = 1e-5


def recall(ids, true_d, ok, ref_ids, ref_d):
    """Per request recall@k against the reference (1.0 where no row
    passes the filter)."""
    n_ref = (ref_ids >= 0).sum(axis=1)
    kth = np.where(n_ref > 0, ref_d[np.arange(len(ref_d)),
                                    np.maximum(n_ref - 1, 0)], np.inf)
    tol = TIE_REL * np.maximum(kth, DIST_FLOOR)
    hit = ok & (true_d <= (kth + tol)[:, None]) & (ids >= 0)
    hits = np.minimum(hit.sum(axis=1), n_ref)
    return np.where(n_ref > 0, hits / np.maximum(n_ref, 1), 1.0)


def bad_id_count(ids, ok, n):
    """Returned ids that are out of range, repeated in a row, or fail
    the filter (-1 padding is no id)."""
    real = ids >= 0
    out_of_range = real & (ids >= n)
    srt = np.sort(np.where(real, ids, -1), axis=1)
    repeats = (srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)
    return int(out_of_range.sum() + repeats.sum() + (real & ~ok).sum())


def numbers(ids, dist, true_d, ok, ref_ids, ref_d, n_rows,
            unfinished: int) -> tuple[dict, np.ndarray]:
    """(numbers compared, per-request recall)."""
    real = ids >= 0
    err = np.abs(dist.astype(np.float64) - true_d) / np.maximum(
        true_d, DIST_FLOOR)
    err = np.where(real & np.isfinite(dist), err, 0.0)
    rec = recall(ids, true_d, ok, ref_ids, ref_d)
    out = {
        "bad_ids": bad_id_count(ids, ok, n_rows),
        "unfinished": int(unfinished),
        "dist_err_max": float(err.max(initial=0.0)),
        "recall_loss": float(1.0 - rec.mean()) if rec.size else 1.0,
    }
    return out, rec


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(every compared number within its limit, {name: {value, limit}}).
    A number with a limit that the run did not produce is not within it."""
    checks, ok = {}, True
    for name, limit in limits.items():
        if name not in nums:
            ok = False
            checks[name] = {"value": None, "limit": limit}
            continue
        v = nums[name]
        checks[name] = {"value": v, "limit": limit}
        ok &= bool(v <= limit)
    return ok, checks
