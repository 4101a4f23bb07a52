"""The comparison that decides ``correct``.

What is compared is what the timed path itself returned in the window:
for every commit, accept or reject, and on a rejection the index the
program named.  Against it stand the generator's own schedule (which
commits carry a flipped signature bit — every commit of the window) and
the plain reference (``reference.py``: ZIP-215 over sign-bytes it
encodes itself), which scans every commit the program rejected or the
schedule tampered — as many as ``max_scans``, drawn from the seed — up
to the first signature it rejects, and verifies a seeded sample of
single signatures from the commits the program accepted.  Every number
is an exact count: its limit is 0.
"""

from __future__ import annotations

import random
import re

from benchmark import gen, reference

_INDEX = re.compile(r"#(\d+)")


def _named_index(err: str) -> int | None:
    m = _INDEX.search(err)
    return int(m.group(1)) if m else None


def compare(chain: gen.Chain, outcomes: list, checked: int, sample: int,
            max_scans: int, rng: random.Random) -> dict:
    """``outcomes``: [(index into chain.items, rejection text or
    None)] in the order the window produced them; ``checked``: how many
    leading signatures the call is bound to look at.
    -> ({name: {"value", "limit"}}, how much the reference looked at)"""
    items = chain.items
    seen = [k for k, _ in outcomes]
    # every item the window reached has exactly one verdict, in order
    expected = list(range(seen[0], seen[0] + len(seen))) if seen else []
    missing = len(set(expected) - set(seen)) + (len(seen) - len(set(seen)))

    def tampered(k: int) -> bool:
        return any(i < checked for i in items[k].bad)

    schedule = sum(
        (err is not None) != tampered(k) for k, err in outcomes
    )
    suspects = [(k, err) for k, err in outcomes
                if err is not None or tampered(k)]
    # disagreements with the schedule first, then a seeded draw
    odd = [s for s in suspects if (s[1] is not None) != tampered(s[0])]
    rest = [s for s in suspects if s not in odd]
    rng.shuffle(rest)
    verdicts = indices = scanned = 0
    for k, err in (odd + rest)[:max_scans]:
        it = items[k]
        msgs = [gen.sign_bytes(it, i) for i in range(checked)]
        ref = reference.first_bad(gen.signers(chain, it)[1], msgs, it.sigs,
                                  checked)
        scanned += 1
        if (ref is not None) != (err is not None):
            verdicts += 1
        elif ref is not None and _named_index(err) != ref:
            indices += 1
    accepted = [k for k, err in outcomes if err is None]
    invalid = 0
    for _ in range(sample if accepted else 0):
        it = items[rng.choice(accepted)]
        i = rng.randrange(checked)
        if not reference.verify_zip215(
            gen.signers(chain, it)[1][i], gen.sign_bytes(it, i), it.sigs[i]
        ):
            invalid += 1

    def exact(value: int) -> dict:
        return {"value": value, "limit": 0}

    compared = {
        "missing_verdicts": exact(missing),
        "schedule_mismatches": exact(schedule),
        "reference_verdict_mismatches": exact(verdicts),
        "reference_index_mismatches": exact(indices),
        "reference_accepted_invalid": exact(invalid),
        # a window with no rejection scanned, or nothing accepted to
        # sample, proved less than its zeros say
        "unexercised_checks": exact(int(scanned == 0) + int(not accepted)),
    }
    return compared, {"reference_scans": scanned,
                      "reference_sampled": sample if accepted else 0}
