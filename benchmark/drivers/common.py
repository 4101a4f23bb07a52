"""What the drivers share: one call through an entry point, a window's
record, a nearest-rank percentile."""

from __future__ import annotations

import math
from dataclasses import dataclass, field

from benchmark import gen


@dataclass
class State:
    """What a driver keeps between warm-up and the stretches of the
    window."""

    chain: gen.Chain
    vals: object
    entry: object  # the program's entry point the window drives
    #: signatures the call is bound to look at, per commit
    checked: int
    #: signatures the program verifies for each item of this traffic
    sigs_per_item: int
    #: (BlockID, Commit) of each of the chain's items, in the program's
    #: types; None once the window is done with it (``consumed``)
    commits: list = field(default_factory=list)
    warm: list = field(default_factory=list)
    cursor: int = 0

    def __post_init__(self) -> None:
        self.warm = [gen.commit_of(self.chain, it) for it in self.chain.warm]
        self.commits = [gen.commit_of(self.chain, it)
                        for it in self.chain.items]

    def consumed(self, k: int) -> None:
        """Let go of item ``k``'s program objects: its verdict is in
        the window's outcomes and the program will not be handed them
        again.  A node drops a block once it is applied; a generator
        that kept its chain alive made every window retain tens of MB
        of commits and the sign-bytes they memoise (PR 31), which no
        node holds.  The frees happen here, inside the window, as they
        do in a node.  ``chain.items`` (plain data, what
        ``check.compare`` reads) stays whole."""
        self.commits[k] = None


def plan(config: dict, params: dict, seed: int, n_items: int) -> gen.Chain:
    """The chain of a driver whose items are consecutive heights."""
    return gen.plan(
        seed, config["validators"], n_items, params["warm"],
        stride=1, tamper_every=params["tamper_every"],
        strata=params["tamper_strata"],
        first_group=params.get("tamper_first_group"),
    )


@dataclass
class Window:
    """One stretch of the measured window."""

    #: (index into the chain's items, rejection text or None), in order
    outcomes: list = field(default_factory=list)
    #: host-clock seconds of each call or step, one an outcome
    latencies: list = field(default_factory=list)
    elapsed: float = 0.0
    ran_out: bool = False
    #: seconds by part of a step, where a driver splits its steps
    parts: dict = field(default_factory=dict)

    def per_quarter(self) -> list:
        """Items finished in each quarter of the time the calls took:
        shows whether a run's pace held or drifted."""
        total = sum(self.latencies)
        counts, acc = [0, 0, 0, 0], 0.0
        if total <= 0:
            return counts
        for s in self.latencies:
            acc += s
            counts[min(3, int(4 * acc / total))] += 1
        return counts

    def extend(self, other: "Window") -> None:
        self.outcomes += other.outcomes
        self.latencies += other.latencies
        self.elapsed += other.elapsed
        for key, sec in other.parts.items():
            self.parts[key] = self.parts.get(key, 0.0) + sec
        self.ran_out = self.ran_out or other.ran_out


def run_verify(fn, vals, bid, commit) -> str | None:
    """One commit through an entry point; the rejection text or None.
    Only a verdict is caught: any other exception is the run's."""
    from cometbft_tpu.types.validation import CommitError

    try:
        fn(gen.CHAIN_ID, vals, bid, commit.height, commit)
    except CommitError as exc:
        return f"{type(exc).__name__}: {exc}"
    return None


def swap_entry(st: State, entry, n_warm: int = 2) -> None:
    """Put a control's entry point in the program's place and compile
    its shapes before the window: through fresh commits from the
    chain's tail (the cache knows the warm-up's), which the window
    then cannot reach.  Their verdicts are not looked at."""
    st.entry = entry
    tail, st.commits = st.commits[-n_warm:], st.commits[:-n_warm]
    for bid, commit in tail:
        run_verify(entry, st.vals, bid, commit)


def percentile(sorted_values: list, pct: float) -> float:
    """Nearest rank: the smallest value with at least ``pct`` percent
    of the samples at or below it."""
    if not sorted_values:
        raise ValueError("no samples")
    rank = max(1, math.ceil(pct / 100.0 * len(sorted_values)))
    return sorted_values[rank - 1]


def expect_warm(item: gen.Item, err: str | None) -> None:
    """A warm-up verdict must be the schedule's: anything else means
    the cell cannot be measured."""
    if (err is not None) != bool(item.bad):
        raise RuntimeError(
            f"warm-up commit at height {item.height}: tampered "
            f"{item.bad}, the program said {err!r}"
        )
