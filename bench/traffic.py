"""The traffic generator: one reader for every mix in ``traffic/``.

A mix names periodic RT tasks (batch, prompt, new tokens, tokens per
decode slice, period, phase and priority, all fixed in the file) and one
best-effort training job released back to back.  From ``--seed`` come
the prompts, the weights and the training rows.
Every release is timed from the moment it was due, so a release that
starts late because the one before overran counts as late."""
from __future__ import annotations

import random
import statistics


def offsets_ms(mix: dict, periods: dict) -> dict:
    """Each RT task's first release, in ms after the window opens: the
    file's ``phase``, a fraction of the task's period in ``periods``
    (task -> ms)."""
    return {t["name"]: t["phase"] * periods[t["name"]] for t in mix["rt"]}


def due_times(first_due: float, period: float, end: float) -> list:
    """Release times first_due + k·period that fall before ``end``."""
    out, t = [], first_due
    while t < end:
        out.append(t)
        t += period
    return out


def outcomes(dues: list, done: dict, deadline: float,
             horizon: float) -> list:
    """(response / deadline, met) for each due release.  ``done[k]`` is
    the completion time of release k; a release that never completed is
    counted as responding at ``horizon`` and missed."""
    out = []
    for k, due in enumerate(dues):
        end = done.get(k)
        resp = (end if end is not None else horizon) - due
        out.append((resp / deadline, end is not None and resp <= deadline))
    return out


def percentile(values: list, q: float) -> float:
    """The q-th percentile (0 < q < 100) by ``statistics.quantiles``'
    inclusive method over 100 cut points; the value itself for one
    sample."""
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=100,
                                      method="inclusive")[int(q) - 1])


def sample(mix: dict, finished: dict, seed: int) -> dict:
    """Releases to re-score against the reference, drawn from the seed
    among those that finished: ``sample_per_task`` of each task (the
    longest task is always in)."""
    rng = random.Random(f"sample:{seed}")
    n = mix["sample_per_task"]
    return {name: sorted(rng.sample(sorted(ks), min(n, len(ks))))
            for name, ks in finished.items() if ks}
