"""A check of each committed proof that uses none of the program's code.

The program's verifier (`verify_bytes`) shares its field, group, IPA
and transcript code with the prover, so it cannot catch a change that
alters both alike.  This module reads the proof bytes with its own
parser of the v3 wire format and holds them to three things, in plain
Python integers:

- **layout**: the framing and the count of every part (commitments,
  openings, sumcheck rounds and round-polynomial degrees, finals, IPA
  rounds, the sigma opening, the total byte length) against the
  configuration's ``proof_layout_by_steps_per_proof``: the protocol's
  layout at this geometry.  A round dropped or an opening shortened
  shows here;
- **range**: every group element (commitments, IPA L/R, the sigma
  opening's A and B) lies in the order-q subgroup of quadratic residues
  mod p = 2q + 1, and every scalar is reduced mod q.  Group arithmetic
  that goes wrong lands outside the subgroup half of the time, per
  element;
- **sumchecks**: the Fiat-Shamir transcript is replayed up to the end
  of the anchor sumcheck, and every round of the matmul-family and
  anchor sumchecks must chain (g_i(0) + g_i(1) equals g_{i-1} at the
  round's challenge), starting from the family targets that the
  openings a1..a6 set; the anchor's last round must equal its finals'
  product form.  Field arithmetic that goes wrong breaks a chain.

What stays with the program's verifier: each family sumcheck's final
against its public coefficients, the anchor's claim and public tables,
and the merged pair IPA's closing equation (they need the graph's
public tables and the 2^19-element generator folds).
"""
from __future__ import annotations

import hashlib
import struct
from typing import Dict, List

#: the proof field F_q and the group's field F_p, p = 2q + 1
Q = 2305843009213688669
P = 2 * Q + 1
MAGIC = b"ZKDL"
VERSION = 3
FAMILIES = ("fwd", "bwd", "gw")
FAMILY_DEGREE = 2
ANCHOR_DEGREE = 3
ANCHOR_FINALS = 5
SIGMA = 5
VALIDITY_COMS = 4


class Malformed(ValueError):
    """The bytes do not frame as a v3 proof."""


class _Reader:
    def __init__(self, data: bytes):
        self.data, self.pos = data, 0

    def take(self, n: int) -> bytes:
        if self.pos + n > len(self.data):
            raise Malformed("truncated")
        self.pos += n
        return self.data[self.pos - n: self.pos]

    def u(self, fmt: str) -> int:
        return struct.unpack("<" + fmt, self.take(struct.calcsize(fmt)))[0]

    def scalars(self, count_fmt: str) -> List[int]:
        n = self.u(count_fmt)
        if self.pos + 8 * n > len(self.data):
            raise Malformed("vector longer than its section")
        return [self.u("Q") for _ in range(n)]

    def named(self, count_fmt: str) -> Dict[str, int]:
        out = {}
        for _ in range(self.u(count_fmt)):
            name = self.take(self.u("H")).decode("utf-8", "replace")
            out[name] = self.u("Q")
        return out

    def sumcheck(self) -> List[List[int]]:
        return [[self.u("Q") for _ in range(self.u("B"))]
                for _ in range(self.u("H"))]

    def done(self) -> bool:
        return self.pos == len(self.data)


def parse(raw: bytes) -> dict:
    """Proof bytes -> plain lists and dicts (raises `Malformed`)."""
    r = _Reader(raw)
    if r.take(4) != MAGIC or r.u("H") != VERSION:
        raise Malformed("not a v3 zkDL proof")
    secs = []
    for tag in range(1, 7):
        if r.u("B") != tag:
            raise Malformed(f"section {tag} out of order")
        secs.append(_Reader(r.take(r.u("I"))))
    if not r.done():
        raise Malformed("trailing bytes")
    meta, coms, opn, sc, fin, ip = secs
    p = {"n_steps": meta.u("I"), "x": coms.scalars("I"),
         "slots": coms.named("H"),
         "validity": [coms.u("Q") for _ in range(VALIDITY_COMS)],
         "openings": opn.named("I"),
         "sc": {f: [sc.sumcheck() for _ in range(sc.u("H"))]
                for f in FAMILIES}}
    p["sc_anchor"] = sc.sumcheck()
    p["finals"], p["claims"] = {}, {}
    for f in FAMILIES:
        p["finals"][f] = [fin.scalars("I") for _ in range(fin.u("H"))]
        p["claims"][f] = fin.scalars("H")
    p["anchor_finals"] = fin.scalars("H")
    n = ip.u("H")
    p["ipa_l"] = [ip.u("Q") for _ in range(n)]
    p["ipa_r"] = [ip.u("Q") for _ in range(n)]
    p["sigma"] = [ip.u("Q") for _ in range(ip.u("B"))]
    if not all(s.done() for s in secs):
        raise Malformed("trailing bytes in a section")
    return p


def layout_bytes(lay: dict) -> int:
    """The wire length a proof of this layout has."""
    names = lambda ns: sum(2 + len(n.encode()) + 8 for n in ns)  # noqa: E731
    n = 4 + 2 + 6 * (1 + 4) + 4                            # header, META
    n += 4 + 8 * lay["x_commitments"] + 2 + names(lay["slots"]) \
        + 8 * VALIDITY_COMS                                # COMS
    n += 4 + names(lay["openings"])                        # OPEN
    for f in FAMILIES:                                     # SC, FINALS
        rounds, pairs = lay["sumcheck_rounds"][f], lay["pairs"][f]
        n += 2 + sum(2 + k * (1 + 8 * (FAMILY_DEGREE + 1)) for k in rounds)
        n += 2 + sum(4 + 8 * 2 * m for m in pairs)
        n += 2 + (8 * len(rounds) if len(rounds) > 1 else 0)
    n += 2 + lay["anchor_rounds"] * (1 + 8 * (ANCHOR_DEGREE + 1))
    n += 2 + 8 * ANCHOR_FINALS
    n += 2 + 16 * lay["ipa_rounds"] + 1 + 8 * SIGMA         # IPA
    return n


def layout_mismatches(p: dict, raw_len: int, lay: dict) -> List[str]:
    """The parts of a parsed proof that differ from the layout."""
    sc_rounds = {f: [len(s) for s in p["sc"][f]] for f in FAMILIES}
    finals = {f: [len(v) for v in p["finals"][f]] for f in FAMILIES}
    degrees = {len(m) - 1 for f in FAMILIES for s in p["sc"][f] for m in s}
    want = {
        "bytes": (raw_len, lay["bytes"]),
        "n_steps": (p["n_steps"], lay["steps"]),
        "x_commitments": (len(p["x"]), lay["x_commitments"]),
        "slots": (list(p["slots"]), lay["slots"]),
        "openings": (sorted(p["openings"]), sorted(lay["openings"])),
        "sumcheck_rounds": (sc_rounds, lay["sumcheck_rounds"]),
        "finals": (finals, {f: [2 * m for m in lay["pairs"][f]]
                            for f in FAMILIES}),
        "claims": ({f: len(p["claims"][f]) for f in FAMILIES},
                   {f: (len(r) if len(r) > 1 else 0)
                    for f, r in lay["sumcheck_rounds"].items()}),
        "family_degree": (degrees <= {FAMILY_DEGREE}, True),
        "anchor_rounds": (len(p["sc_anchor"]), lay["anchor_rounds"]),
        "anchor_degree": ({len(m) - 1 for m in p["sc_anchor"]}
                          <= {ANCHOR_DEGREE}, True),
        "anchor_finals": (len(p["anchor_finals"]), ANCHOR_FINALS),
        "ipa_rounds": ((len(p["ipa_l"]), len(p["ipa_r"])),
                       (lay["ipa_rounds"],) * 2),
        "sigma": (len(p["sigma"]), SIGMA),
    }
    return [k for k, (got, exp) in want.items() if got != exp]


def _in_group(v: int) -> bool:
    return 0 < v < P and pow(v, Q, P) == 1


def out_of_range(p: dict) -> int:
    """Group elements outside the order-q subgroup, and scalars not
    reduced mod q."""
    group = (p["x"] + list(p["slots"].values()) + p["validity"]
             + p["ipa_l"] + p["ipa_r"] + p["sigma"][:2])
    scalars = (list(p["openings"].values()) + p["anchor_finals"]
               + p["sigma"][2:]
               + [v for f in FAMILIES for s in p["sc"][f] for m in s
                  for v in m]
               + [v for m in p["sc_anchor"] for v in m]
               + [v for f in FAMILIES for fs in p["finals"][f] for v in fs]
               + [v for f in FAMILIES for v in p["claims"][f]])
    return (sum(not _in_group(v) for v in group)
            + sum(not 0 <= v < Q for v in scalars))


class Transcript:
    """SHA-256 Fiat-Shamir transcript of the zkDL protocol, as its
    paper-level description fixes it: absorb hashes the running state
    with a length-framed label and payload; a challenge hashes the state
    with a label and a counter, widened to 512 bits, mod the field."""

    def __init__(self, label: bytes):
        self.state = hashlib.sha256(label).digest()
        self.counter = 0

    def absorb(self, label: bytes, values: List[int]) -> None:
        data = b"".join(int(v).to_bytes(32, "little") for v in values)
        self.state = hashlib.sha256(
            self.state + len(label).to_bytes(4, "little") + label
            + len(data).to_bytes(8, "little") + data).digest()

    def challenge(self, label: bytes) -> int:
        d = hashlib.sha256(
            self.state + b"challenge" + len(label).to_bytes(4, "little")
            + label + self.counter.to_bytes(8, "little")).digest()
        self.counter += 1
        return int.from_bytes(d + hashlib.sha256(d).digest(), "little") % Q


def lagrange(ys: List[int], x: int) -> int:
    """The polynomial through (0, ys[0]), (1, ys[1]), ... at x, mod q."""
    acc = 0
    for i, y in enumerate(ys):
        num = den = 1
        for j in range(len(ys)):
            if j != i:
                num = num * (x - j) % Q
                den = den * (i - j) % Q
        acc = (acc + y * num * pow(den, Q - 2, Q)) % Q
    return acc


def _chain(t: Transcript, label: bytes, msgs, running) -> tuple:
    """Replays one sumcheck; ``running`` None skips the first round's
    claim.  Returns (failed rounds, the value the finals must meet)."""
    failed = 0
    for m in msgs:
        if running is not None and (m[0] + m[1]) % Q != running:
            failed += 1
        t.absorb(label + b"/round", m)
        running = lagrange(m, t.challenge(label + b"/r"))
    return failed, running


def sumcheck_failures(p: dict, lay: dict, label: bytes, q_bits: int,
                      r_bits: int) -> int:
    """Round equations of the replayed sumchecks that do not hold."""
    t = Transcript(label)
    t.absorb(b"coms", p["x"] + list(p["slots"].values()) + p["validity"])
    t.counter += lay["schedule_challenges"]
    op = p["openings"]
    a = [op[k] for k in ("a1", "a2", "a3", "a4", "a5", "a6")]
    t.absorb(b"op1", a)
    targets = {
        "fwd": ((1 << r_bits) * a[0] - (1 << (q_bits + r_bits - 1)) * a[1]
                + a[2]) % Q,
        "bwd": ((1 << r_bits) * a[3] + a[4]) % Q,
        "gw": a[5] % Q}
    failed = 0
    for f in FAMILIES:
        lab = f.encode()
        claims = p["claims"][f]
        if len(p["sc"][f]) == 1:
            claims = [targets[f]]
        else:
            failed += sum(claims) % Q != targets[f]
            t.absorb(lab + b"/claims", claims)
        for claim, msgs, finals in zip(claims, p["sc"][f], p["finals"][f]):
            failed += _chain(t, lab, msgs, claim % Q)[0]
            t.absorb(lab + b"/final", finals)
    t.counter += lay["anchor_challenges"]
    # the anchor's first claim is the graph's public tables' to check
    bad, last = _chain(t, b"anchor", p["sc_anchor"], None)
    oneb, zpp, gap, pa, pg = p["anchor_finals"]
    return (failed + bad
            + (last != (oneb * pa % Q * zpp + oneb * pg % Q * gap) % Q))


def check(raws: List[bytes], lay: dict, label: bytes, q_bits: int,
          r_bits: int) -> Dict[str, int]:
    """The three numbers, summed over the run's committed proofs."""
    out = {"proof_layout_mismatches": 0, "proof_elements_out_of_range": 0,
           "sumcheck_equations_failed": 0}
    for raw in raws:
        try:
            p = parse(raw)
            miss = layout_mismatches(p, len(raw), lay)
        except Malformed:
            out["proof_layout_mismatches"] += 1
            continue
        out["proof_layout_mismatches"] += len(miss)
        out["proof_elements_out_of_range"] += out_of_range(p)
        if not miss:        # the replay needs the parts where it expects
            out["sumcheck_equations_failed"] += sumcheck_failures(
                p, lay, label, q_bits, r_bits)
    return out
