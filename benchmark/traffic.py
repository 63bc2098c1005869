"""The one traffic generator: a configuration and a mix in, wire payloads out.

A configuration (``benchmark/configs/<name>.json``) describes the
deployment: the validator registry, committees, and the signature sets
that make up one *unit* of traffic (an attestation, an aggregate and its
proofs, a block's sets).  A mix (``benchmark/traffic/<name>.json``)
describes how units arrive: open loop on a schedule or closed loop with
a fixed number of submissions in flight, submission sizes, tenants,
poisoned sets, and whether the keys are warmed through the edge first.
Both are data; this module reads them and builds, from ``--seed`` alone:

* the compressed public keys of every validator the run uses,
* every submission with its wire sets and the verdict each set must get,
  known by construction (a valid signature, or one poisoned on purpose),
* the warm-up submissions that carry every registry key through the edge
  (``"warm_keys": "all"``, the default) or none (``"none"``: keys reach
  the service fresh).

Configuration knobs:

* ``unit.per_committee``: units per committee and slot, each signed by
  its own member; or ``unit.per_slot``: units per slot, spread over the
  slot's committees;
* per set in ``unit.sets``: ``signers`` (``unit_member``, the unit's own
  key; ``participants``, a share of a committee; ``sync_committee``, a
  share of one committee of ``sync_committee_size`` keys drawn per run),
  ``message`` (``committee``, ``slot`` or ``unit``, a root drawn from
  ``unit_message_pool``), ``count`` (the set repeated over that many
  consecutive committees of the slot) and ``participation``
  (``[lo, hi]`` share of the committee, default the unit's).

Mix knobs: ``loop`` (``open`` with ``rate_sets_per_s``, or ``closed``
with ``capacity_sets_per_s``, ``tenants`` x ``in_flight_per_tenant``),
``arrivals`` for the open loop (``{"kind": "poisson"}``, the default;
``{"kind": "uniform"}``; or ``{"kind": "bursts", "period_s": 12,
"bursts": [{"at_s": 4, "width_s": 1, "share": 0.6}]}``: that share of
submissions lands in a burst of each period, the rest anywhere in it),
``submission_units`` (a number, or ``{"log_uniform": [lo, hi]}``),
``tenant_zipf``, ``poison``, ``poison_span``, ``warm_keys``.

Every seed gets the same multiset of submission sizes, gaps and tenants
(drawn from the mix's ``shape_seed``) in its own order, so seeds change
which keys, messages and positions are used but not how much work a run
does.  Signing runs in worker processes (``pool.map``) and never touches
JAX: secret keys are small consecutive integers, so each signature is a
short walk over precomputed multiples of its message point.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import random
from dataclasses import dataclass, field

from . import bls_ref as B

HERE = os.path.dirname(os.path.abspath(__file__))

#: secret keys are ``base + index`` with ``base`` below this, so every
#: signing scalar fits the precomputed doublings of ``G2Multiples``
SK_BASE_BITS = 34
WALK_TABLE = 64


def load(kind: str, name: str, root: str = HERE) -> dict:
    """``benchmark/<kind>/<name>.json`` as a dict."""
    with open(os.path.join(root, kind, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def _root(*parts) -> bytes:
    return hashlib.sha256("|".join(map(str, parts)).encode()).digest()


@dataclass
class WireSet:
    sig: bytes
    keys: list          # indices into Traffic.pubkeys
    msg: bytes
    expected: bool
    poison: str | None = None


@dataclass
class Submission:
    tenant: str
    due: float | None   # seconds after the window opens (open loop)
    sets: list = field(default_factory=list)


@dataclass
class Traffic:
    pubkeys: list       # compressed keys of the validators used, bytes
    warmup: list        # submissions, before the window
    submissions: list   # in send order
    loop: str
    mix: dict

    @property
    def n_sets(self) -> int:
        return sum(len(s.sets) for s in self.submissions)


# ---------------------------------------------------------------------------
# Shapes: the same multiset for every seed
# ---------------------------------------------------------------------------


def _draw_size(rng: random.Random, spec) -> int:
    if isinstance(spec, int):
        return spec
    lo, hi = spec["log_uniform"]
    return min(hi, int(math.exp(rng.uniform(math.log(lo), math.log(hi + 1)))))


def _tenant_weights(n: int, s: float) -> list:
    w = [1.0 / (k + 1) ** s for k in range(n)]
    total = sum(w)
    return [x / total for x in w]


def _burst_dues(rng: random.Random, arrivals: dict, seconds: float,
                n: int) -> list:
    """``n`` sorted due times inside ``[0, seconds)``: a submission falls
    in burst ``b`` of a period drawn at random with probability
    ``b["share"]``, uniformly inside its ``width_s`` from ``at_s``, and
    otherwise anywhere in that period."""
    period = arrivals["period_s"]
    periods = max(1, math.ceil(seconds / period))
    dues = []
    while len(dues) < n:
        start = period * rng.randrange(periods)
        u = rng.random()
        for burst in arrivals["bursts"]:
            if u < burst["share"]:
                t = start + burst["at_s"] + rng.uniform(0.0, burst["width_s"])
                break
            u -= burst["share"]
        else:
            t = start + rng.uniform(0.0, period)
        if t < seconds:
            dues.append(t)
    return sorted(dues)


def shape(mix: dict, seconds: float, sets_per_unit: int):
    """(sizes in units, tenant index per submission, gaps, dues) before the
    run seed reorders them.  Open loop: enough submissions for the offered
    rate over the window; with Poisson or uniform arrivals ``gaps`` are
    ``len(sizes) + 1`` spacings summing to ``seconds`` (Poisson: of a
    Poisson process conditioned on that count), which the run seed
    shuffles; with bursts ``dues`` are fixed, sorted due times.  Closed
    loop: enough for ``capacity_sets_per_s`` over the window, neither."""
    rng = random.Random(mix["shape_seed"])
    if mix["loop"] == "open":
        want_units = mix["rate_sets_per_s"] * seconds / sets_per_unit
    else:
        want_units = mix["capacity_sets_per_s"] * seconds / sets_per_unit
    sizes = []
    while sum(sizes) < want_units:
        sizes.append(_draw_size(rng, mix["submission_units"]))
    weights = _tenant_weights(mix["tenants"], mix.get("tenant_zipf", 0.0))
    tenants = rng.choices(range(mix["tenants"]), weights=weights, k=len(sizes))
    gaps = dues = None
    if mix["loop"] == "open":
        arrivals = mix.get("arrivals", {"kind": "poisson"})
        kind = arrivals["kind"]
        if kind == "bursts":
            dues = _burst_dues(rng, arrivals, seconds, len(sizes))
        elif kind in ("poisson", "uniform"):
            raw = ([rng.expovariate(1.0) for _ in range(len(sizes) + 1)]
                   if kind == "poisson" else [1.0] * (len(sizes) + 1))
            scale = seconds / sum(raw)
            gaps = [g * scale for g in raw]
        else:
            raise ValueError(f"unknown arrivals {kind!r}")
    return sizes, tenants, gaps, dues


# ---------------------------------------------------------------------------
# Plan: who signs what (cheap, deterministic, no curve arithmetic)
# ---------------------------------------------------------------------------


class _Messages:
    """Distinct message roots and the sign tasks per message."""

    def __init__(self):
        self.index: dict = {}
        self.items: list = []       # [(msg, [(set_id, scalar, extra)])]

    def add(self, msg: bytes, set_id: int, scalar: int, extra: int = 0):
        k = self.index.get(msg)
        if k is None:
            k = self.index[msg] = len(self.items)
            self.items.append((msg, []))
        self.items[k][1].append((set_id, scalar, extra))


def plan(config: dict, mix: dict, seed: int, seconds: float):
    """(sets, submissions, warm-up set ids, messages, base, used keys).
    Everything a run sends, as indices and scalars; ``build`` turns it
    into bytes.  A set's key indices point into ``used``, the sorted
    registry indices of every validator the run uses."""
    rng = random.Random(seed)
    n_keys = config["validators"]
    base = rng.randrange(1 << (SK_BASE_BITS - 1), 1 << SK_BASE_BITS)
    outside = rng.randrange(1 << (SK_BASE_BITS - 1), 1 << SK_BASE_BITS)
    while base <= outside < base + n_keys:
        outside = rng.randrange(1 << (SK_BASE_BITS - 1), 1 << SK_BASE_BITS)
    unit_spec = config["unit"]
    sets_per_unit = sum(spec.get("count", 1) for spec in unit_spec["sets"])
    n_comm = config["committees_per_slot"]
    per_committee = unit_spec.get("per_committee")
    per_slot = unit_spec.get("per_slot") or n_comm * per_committee

    sizes, tenants, gaps, dues = shape(mix, seconds, sets_per_unit)
    order = list(range(len(sizes)))
    rng.shuffle(order)
    sizes = [sizes[i] for i in order]
    tenants = [tenants[i] for i in order]
    if gaps is not None:
        rng.shuffle(gaps)
    n_units = sum(sizes)

    # units, slot by slot; within a slot every committee's units interleave
    units = []          # (slot, committee, member index)
    n_slots = -(-n_units // per_slot)
    committees = {}
    for slot in range(n_slots):
        slot_units = []
        for c in range(n_comm):
            members = rng.sample(range(n_keys), config["committee_size"])
            committees[(slot, c)] = members
            if "per_slot" not in unit_spec:
                slot_units += [(slot, c, m) for m in members[:per_committee]]
        if "per_slot" in unit_spec:
            slot_units = [(slot, k % n_comm, committees[(slot, k % n_comm)][k // n_comm])
                          for k in range(per_slot)]
        rng.shuffle(slot_units)
        units += slot_units
    units = units[:n_units]

    sync: list = []

    def sync_committee() -> list:
        if not sync:
            sync.extend(rng.sample(range(n_keys), config["sync_committee_size"]))
        return sync

    unit_part = unit_spec.get("participation", [1.0, 1.0])
    unit_pool = [_root(seed, "unit-root", k)
                 for k in range(config.get("unit_message_pool", 1))]
    msgs = _Messages()
    sets = []           # [msg, key indices, scalar, poison kind, extra]
    unit_sets = []
    for slot, c, member in units:
        ids = []
        for spec in unit_spec["sets"]:
            lo, hi = spec.get("participation", unit_part)
            for j in range(spec.get("count", 1)):
                cc = (c + j) % n_comm
                kind = spec["message"]
                if kind == "committee":
                    msg = _root(seed, "attestation-data", slot, cc)
                elif kind == "slot":
                    msg = _root(seed, "slot", slot)
                else:
                    msg = unit_pool[rng.randrange(len(unit_pool))]
                signers = spec["signers"]
                if signers == "unit_member":
                    keys = [member]
                else:
                    if signers == "participants":
                        members = committees[(slot, cc)]
                    elif signers == "sync_committee":
                        members = sync_committee()
                    else:
                        raise ValueError(f"unknown signers {signers!r}")
                    k = rng.randint(math.ceil(lo * len(members)),
                                    math.floor(hi * len(members)))
                    keys = sorted(rng.sample(members, k))
                ids.append(len(sets))
                sets.append([msg, keys, sum(base + i for i in keys), None, 0])
        unit_sets.append(ids)

    # poison: the k-th of K poisoned submissions sits at the fixed point
    # (k + 1/2) / K of the window (open loop, by due time) or of the first
    # ``poison_span`` of the stream (closed loop, by position), so every
    # seed stalls the ladder at the same moments; the seed picks the sets
    poison = mix["poison"]
    kinds = (["wrong_message"] * poison.get("wrong_message", 0)
             + ["outside_signer"] * poison.get("outside_signer", 0)
             + ["cancelling_pair"] * poison.get("cancelling_pairs", 0))
    starts = [0]
    for s in sizes:
        starts.append(starts[-1] + s)
    if gaps is not None:
        dues, t = [], 0.0
        for g in gaps[:len(sizes)]:
            t += g
            dues.append(t)
    span = max(len(kinds), int(mix.get("poison_span", 1.0) * len(sizes)))
    taken = set()
    for k, kind in enumerate(kinds):
        frac = (k + 0.5) / len(kinds)
        first = (next((i for i, d in enumerate(dues) if d >= frac * seconds),
                      len(dues) - 1)
                 if dues else int(frac * span))
        order = sorted(range(len(sizes)), key=lambda i: (i < first, abs(i - first)))
        sub = next(i for i in order
                   if i not in taken and (kind != "cancelling_pair"
                                          or sizes[i] * sets_per_unit >= 2))
        taken.add(sub)
        sub_sets = [sid for u in range(starts[sub], starts[sub + 1])
                    for sid in unit_sets[u]]
        sub_sets = [sid for sid in sub_sets if sets[sid][3] is None]
        if kind == "cancelling_pair":
            a, b = rng.sample(sub_sets, 2)
            t = rng.randrange(1, 1 << 40)
            sets[a][3], sets[a][4] = kind, t
            sets[b][3], sets[b][4] = kind, -t
        elif kind == "wrong_message":
            sid = rng.choice(sub_sets)
            sets[sid][3] = kind
        else:
            sid = rng.choice(sub_sets)
            sets[sid][3] = kind
            sets[sid][2] += outside

    for sid, (msg, keys, scalar, kind, extra) in enumerate(sets):
        signed = msg + b"/wrong" if kind == "wrong_message" else msg
        msgs.add(signed, sid, scalar, extra)

    submissions = []
    for k in range(len(sizes)):
        sids = [sid for u in range(starts[k], starts[k + 1])
                for sid in unit_sets[u]]
        submissions.append((f"tenant-{tenants[k]}",
                            dues[k] if dues else None, sids))

    # warm-up: every registry key once, in valid aggregate sets
    warm = []
    warm_keys = mix.get("warm_keys", "all")
    if warm_keys not in ("all", "none"):
        raise ValueError(f"unknown warm_keys {warm_keys!r}")
    if warm_keys == "all":
        width = config["committee_size"]
        wmsg = _root(seed, "warm-up")
        for start in range(0, n_keys, width):
            keys = list(range(start, min(n_keys, start + width)))
            sid = len(sets)
            sets.append([wmsg, keys, sum(base + i for i in keys), None, 0])
            msgs.add(wmsg, sid, sets[sid][2], 0)
            warm.append(sid)

    # only the keys the run uses are made: index them densely
    used = sorted({i for s in sets for i in s[1]})
    pos = {k: j for j, k in enumerate(used)}
    for s in sets:
        s[1] = [pos[i] for i in s[1]]
    return sets, submissions, warm, msgs, base, used


# ---------------------------------------------------------------------------
# Signing (worker processes)
# ---------------------------------------------------------------------------


def sign_message(item):
    """Compressed signatures for every set signed over one message:
    ``[(set_id, sig_bytes)]``.  Scalars are walked in sorted order so
    most signatures cost one addition of a small multiple of H(m)."""
    msg, tasks = item
    h = B.hash_to_g2(msg)
    table = B.G2Multiples(h, WALK_TABLE, bits=SK_BASE_BITS + 11)
    out = []
    prev_k, prev = 0, (B.F2_ONE, B.F2_ONE, B.F2_ZERO)
    for set_id, scalar, extra in sorted(tasks, key=lambda t: t[1]):
        step = scalar - prev_k
        prev = B.g2_add(prev, table.mul(step))
        prev_k = scalar
        pt = prev
        if extra:
            d = B.g2_mul(B.G2_GEN, abs(extra))
            pt = B.g2_add(pt, d if extra > 0 else B.g2_neg(d))
        out.append((set_id, B.g2_compress(B.g2_affine(pt))))
    return out


def pool_pubkeys(base: int, indices: list) -> list:
    """Compressed keys of secret keys ``base + i`` for the sorted
    ``indices``, walked one step of ``G1`` multiples at a time."""
    out = []
    pt = at = None
    for i in indices:
        if pt is None:
            pt = B.g1_mul(B.G1_GEN, base + i)
        else:
            gap = i - at
            pt = B.g1_add(pt, B.G1_GEN if gap == 1 else B.g1_mul(B.G1_GEN, gap))
        at = i
        out.append(B.g1_compress(B.g1_affine(pt)))
    return out


def build(config: dict, mix: dict, seed: int, seconds: float,
          mapper=map) -> Traffic:
    """The whole run's traffic.  ``mapper`` is ``map`` or a process
    pool's ``map``; the result does not depend on which."""
    sets, subs, warm, msgs, base, used = plan(config, mix, seed, seconds)
    sigs = {}
    for chunk in mapper(sign_message, msgs.items):
        sigs.update(chunk)
    pubkeys = pool_pubkeys(base, used)

    def wire(sid):
        msg, keys, _scalar, kind, _extra = sets[sid]
        return WireSet(sigs[sid], keys, msg, kind is None, kind)

    submissions = [Submission(t, due, [wire(s) for s in sids])
                   for t, due, sids in subs]
    warmup = [Submission("warm-up", None, [wire(s)]) for s in warm]
    return Traffic(pubkeys, warmup, submissions, mix["loop"], mix)


# ---------------------------------------------------------------------------
# Wire form
# ---------------------------------------------------------------------------


def body(sub: Submission, pubkeys_hex: list, deadline_ms=None) -> bytes:
    """The POST body of one submission, as ``tools/serve.py`` takes it."""
    doc = {"tenant": sub.tenant, "sets": [
        {"signature": "0x" + s.sig.hex(),
         "pubkeys": [pubkeys_hex[i] for i in s.keys],
         "message": "0x" + s.msg.hex()} for s in sub.sets]}
    if deadline_ms is not None:
        doc["deadline_ms"] = deadline_ms
    return json.dumps(doc).encode()


def to_file(traffic: Traffic, path: str) -> None:
    """The load generator's input: keys once, submissions as indices."""
    doc = {
        "loop": traffic.loop,
        "mix": traffic.mix,
        "pubkeys": ["0x" + k.hex() for k in traffic.pubkeys],
        "submissions": [
            {"tenant": s.tenant, "due": s.due,
             "sets": [[w.sig.hex(), w.keys, w.msg.hex()] for w in s.sets]}
            for s in traffic.submissions],
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, separators=(",", ":"))
