"""Whether a run is correct: what the timed path returned, against the yardstick.

Every number compared has the limit 0:

* ``wrong_verdicts``: sets whose verdict differs from the one known by
  construction (a valid signature, or a set poisoned on purpose: signed
  over another message, by a key outside the set, or one of a pair whose
  signatures are off by +D and -D, which only the random weights of batch
  verification tell apart);
* ``unanswered``: accepted requests with no verdict a minute after the
  window;
* ``reference_disagreements``: sets of a sample, drawn from the seed,
  whose served verdict differs from the plain reference's
  (``bls_ref.verify_set``), run after the program's state is freed; the
  sample holds every poisoned set and the set with the most keys;
* ``non_device_verdicts``: batches or sets answered by anything but the
  device rung (CPU rung, integrity re-ladder, guard backstop, failed
  dispatch), from the program's counters over the window;
* ``window_compiles``: programs traced or compiled inside the window;
* ``warmup_wrong``: warm-up submissions with a wrong verdict;
* ``generator_exhausted``: a closed loop that ran out of submissions.
"""

from __future__ import annotations

import random
from dataclasses import dataclass

from . import bls_ref

LIMITS = {
    "wrong_verdicts": 0,
    "unanswered": 0,
    "reference_disagreements": 0,
    "non_device_verdicts": 0,
    "window_compiles": 0,
    "warmup_wrong": 0,
    "generator_exhausted": 0,
}


@dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    checks: dict
    sets_ok: list          # per loadgen record: sets with a right verdict
    sample_size: int
    poisoned_answered: int


def reference_verdict(item) -> bool:
    sig, pubkeys, msg = item
    return bls_ref.verify_set(sig, pubkeys, msg, key_cache={})


def sample(answered: list, k: int, seed: int) -> list:
    """Every poisoned set, the set with the most keys, and ``k`` others
    drawn from the seed."""
    rng = random.Random(seed ^ 0x5EED_CAFE)
    chosen = [i for i, a in enumerate(answered) if a[1].poison is not None]
    if answered:
        longest = max(range(len(answered)),
                      key=lambda i: len(answered[i][1].keys))
        if longest not in chosen:
            chosen.append(longest)
    taken = set(chosen)
    rest = [i for i in range(len(answered)) if i not in taken]
    chosen += rng.sample(rest, min(k, len(rest)))
    return [answered[i] for i in chosen]


def judge(traffic, win, seed: int, warmup_wrong: int, mapper=map) -> Result:
    wrong = unanswered = failed = 0
    sets_ok = []
    answered = []
    for rec in win.records:
        sub = traffic.submissions[rec["idx"]]
        verdicts = rec.get("verdicts")
        if rec["status"] != 202:
            failed += 1
            sets_ok.append(0)
            continue
        if verdicts is None or len(verdicts) != len(sub.sets):
            unanswered += 1
            failed += 1
            sets_ok.append(0)
            continue
        bad = sum(v != s.expected for v, s in zip(verdicts, sub.sets))
        wrong += bad
        failed += bad > 0
        sets_ok.append(len(sub.sets) - bad)
        answered += [(rec["idx"], s, v) for s, v in zip(sub.sets, verdicts)]

    picked = sample(answered, traffic.mix["reference_sample"], seed)
    keys = traffic.pubkeys
    refs = list(mapper(reference_verdict, [
        (s.sig, [keys[i] for i in s.keys], s.msg) for _idx, s, _v in picked]))
    disagree = sum(ref != bool(v) for ref, (_i, _s, v) in zip(refs, picked))

    b, a = win.before, win.after
    non_device = (sum(a["rungs"][n] - b["rungs"][n] for n in a["rungs"])
                  + a["cpu_journal"] - b["cpu_journal"])
    compiles = (a["compiles"] - b["compiles"]
                + a["jit_compiles"] - b["jit_compiles"])
    values = {
        "wrong_verdicts": wrong,
        "unanswered": unanswered,
        "reference_disagreements": disagree,
        "non_device_verdicts": int(non_device),
        "window_compiles": int(compiles),
        "warmup_wrong": warmup_wrong,
        "generator_exhausted": int(bool(win.loadgen.get("exhausted"))),
    }
    checks = {n: {"value": values[n], "limit": LIMITS[n]} for n in LIMITS}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return Result(correct, len(win.records), failed, checks, sets_ok,
                  len(picked), sum(s.poison is not None for _i, s, _v in answered))
