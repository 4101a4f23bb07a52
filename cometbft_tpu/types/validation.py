"""Commit verification — the north-star hot path (types/validation.go).

All block/light-client/evidence verification funnels here, and from
here into the BatchVerifier seam, i.e. onto the TPU:

  VerifyCommit              every applied block (state/validation.go:94)
  VerifyCommitLight         blocksync replay (internal/blocksync/reactor.go:550)
  VerifyCommitLightTrusting light client (light/verifier.go:56)

Design difference from the reference: its batch path gets only a single
ok/fail bit from the RLC batch equation and must re-verify sequentially
to find the offender (types/validation.go:310); the data-parallel device
kernel returns per-signature validity, so the invalid index is free.
"""

from __future__ import annotations

from dataclasses import dataclass
from fractions import Fraction

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import verify_queue as _vq
from cometbft_tpu.types.block import BlockID, Commit
from cometbft_tpu.types.validator import ValidatorSet
from cometbft_tpu.utils import sync as cmtsync
from cometbft_tpu.utils import trustguard
from cometbft_tpu.utils.flight import FLIGHT
from cometbft_tpu.utils.trace import TRACER as _tracer


class CommitError(Exception):
    pass


class InvalidCommitHeight(CommitError):
    pass


class InvalidCommitSignatures(CommitError):
    pass


class NotEnoughVotingPower(CommitError):
    pass


@dataclass
class _Entry:
    idx: int
    val_idx: int
    power: int
    counts: bool  # counts toward the tallied (for-block) power
    #: covered by the commit-level BLS aggregate (Commit.agg_signature)
    #: — tallies power like any entry but is excluded from the per-
    #: signature crypto groups: its proof is the ONE pairing-product
    aggregated: bool = False


def _check_dims(vals: ValidatorSet, commit: Commit, height: int, block_id: BlockID):
    if vals is None or commit is None:
        raise CommitError("nil validator set or commit")
    if height != commit.height:
        raise InvalidCommitHeight(
            f"commit height {commit.height}, expected {height}"
        )
    if block_id != commit.block_id:
        raise InvalidCommitSignatures(
            f"commit for wrong block id {commit.block_id}"
        )


def _batch_groups(entries: list[_Entry], vals) -> list[list[_Entry]]:
    """Group entries by pubkey type for the crypto pass.

    The reference batches only when the whole commit shares one
    batch-capable key type and otherwise verifies serially
    (validation.go:15 shouldBatchVerify); grouping instead means a
    mixed ed25519+bls12381 commit still gets ONE device launch for
    its ed25519 votes and ONE multi-pairing for its BLS votes — the
    BASELINE mega-commit shape."""
    groups: dict[str, list[_Entry]] = {}
    for e in entries:
        if e.aggregated:
            continue  # proven by the commit-level aggregate check
        groups.setdefault(
            vals.get_by_index(e.val_idx).pub_key.type(), []
        ).append(e)
    return list(groups.values())


def _verify(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    count_sig,
    count_all: bool,
    lookup_by_address: bool,
    mode: str,
    signer_vals: ValidatorSet | None = None,
) -> None:
    """Shared engine for the three verification modes
    (validation.go:160 verifyBasicValsAndCommit + verifyCommitBatch).

    count_sig(cs) decides which signatures are cryptographically checked;
    tallied power only ever counts BlockIDFlagCommit votes. count_all
    keeps verifying past the threshold (VerifyCommit) or stops early
    (the Light variants).  ``mode`` (full/light/trusting) names the
    caller on the ``verify_commit`` span, which covers all of this.

    When the commit carries ``agg_signature`` (types/block.py), the
    covered COMMIT-flag votes are proven by ONE BLS pairing-product
    check over their signers instead of per-signature batches — the
    verify path is picked by what the commit actually carries.  In
    by-address (trusting) mode the aggregate equation needs signers
    OUTSIDE the tally set too: ``signer_vals`` (the untrusted block's
    own validator set, passed by light/verifier.py) resolves their
    pubkeys; signature validity comes from the aggregate, tallied
    power still counts only validators matched in ``vals``.
    """
    with _tracer.span(
        "verify_commit", cat="crypto", height=commit.height, mode=mode,
    ) as root:
        with _tracer.span("verify_commit/collect", cat="crypto"):
            entries, agg_pubs = _collect_entries(
                vals, commit, voting_power_needed, count_sig, count_all,
                lookup_by_address, signer_vals,
            )
            groups = _batch_groups(entries, vals)
        _crypto_pass(chain_id, vals, commit, entries, agg_pubs, groups, root)
        tallied = sum(e.power for e in entries if e.counts)
        if tallied <= voting_power_needed:
            raise NotEnoughVotingPower(
                f"tallied {tallied} <= needed {voting_power_needed}"
            )


def _collect_entries(
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    count_sig,
    count_all: bool,
    lookup_by_address: bool,
    signer_vals: ValidatorSet | None,
) -> tuple[list[_Entry], list]:
    """The signatures ``_verify`` will check, in commit order, and
    every signer of the commit-level aggregate equation."""
    if not lookup_by_address and len(vals) != commit.size():
        raise InvalidCommitSignatures(
            f"validator set size {len(vals)} != commit size {commit.size()}"
        )

    has_agg = bool(commit.agg_signature)
    entries: list[_Entry] = []
    agg_pubs: list = []  # every signer in the aggregate equation
    counted_power = 0
    seen_addrs: set[bytes] = set()
    for idx, cs in enumerate(commit.signatures):
        if not count_sig(cs):
            continue
        aggregated = has_agg and cs.is_commit() and not cs.signature
        if lookup_by_address:
            val_idx, val = vals.get_by_address(cs.validator_address)
            if val_idx < 0:
                if aggregated:
                    # not in the tally set, but the pairing equation
                    # still needs this signer's pubkey — an aggregate
                    # over S only verifies against exactly S
                    s_idx, s_val = (-1, None)
                    if signer_vals is not None:
                        s_idx, s_val = signer_vals.get_by_address(
                            cs.validator_address
                        )
                    if s_idx < 0 or s_val is None:
                        raise InvalidCommitSignatures(
                            f"cannot resolve aggregate signer "
                            f"{cs.validator_address.hex()[:12]} "
                            "(no signer set for trusting verification)"
                        )
                    agg_pubs.append(s_val.pub_key)
                continue
            if cs.validator_address in seen_addrs:
                raise InvalidCommitSignatures(
                    "double vote by validator in trusting verification"
                )
            seen_addrs.add(cs.validator_address)
        else:
            val_idx, val = idx, vals.get_by_index(idx)
            if val is None:
                raise InvalidCommitSignatures(f"no validator at index {idx}")
            if val.address != cs.validator_address:
                raise InvalidCommitSignatures(
                    f"signature {idx} address mismatch"
                )
        if aggregated:
            agg_pubs.append(val.pub_key)
        entries.append(
            _Entry(
                idx, val_idx, val.voting_power, cs.is_commit(),
                aggregated=aggregated,
            )
        )
        if cs.is_commit():
            counted_power += val.voting_power
        # early-break path: stop collecting once the counted power
        # passes the threshold (validation.go:290).  Disabled for
        # aggregate commits: the pairing equation needs EVERY covered
        # signer collected, so breaking early would verify the
        # aggregate against a truncated signer list and reject a
        # valid commit.
        if (
            not count_all and not has_agg
            and counted_power > voting_power_needed
        ):
            break
    return entries, agg_pubs


def commit_check_triples(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int | None = None,
    span=None,
) -> list | None:
    """The ``(pub_key, sign_bytes, signature)`` triples a by-index check
    of ``commit`` against ``vals`` will look at, in commit order: its
    COMMIT-flag votes, up to and including the first that takes the
    tallied power past ``voting_power_needed`` (``None``: all of them)
    — what ``verify_commit_light`` batches, for the callers that
    submit a commit's signatures to the verify queue ahead of its
    check (blocksync prefetch, the light serving plane, the light
    client's verify-ahead).  None when the commit does not line up
    with ``vals`` (the set rotated, a malformed commit: never guess —
    the check itself reports the precise error).  Votes covered by a
    commit-level BLS aggregate carry no per-signature proof and are
    skipped.  ``span``, where given, records ``encoded`` and
    ``generic`` (``Commit.vote_sign_bytes_many``'s counts)."""
    if commit is None or commit.size() != len(vals):
        return None
    idxs = []
    pub_keys = []
    power = 0
    for i, cs in enumerate(commit.signatures):
        if not cs.is_commit() or commit.is_aggregated(i):
            continue
        val = vals.get_by_index(i)
        if val is None or val.address != cs.validator_address:
            return None
        idxs.append(i)
        pub_keys.append(val.pub_key)
        power += val.voting_power
        if voting_power_needed is not None and power > voting_power_needed:
            break
    sbs, encoded, generic = commit.vote_sign_bytes_many(chain_id, idxs)
    if span is not None:
        span.set(encoded=encoded, generic=generic)
    sigs = commit.signatures
    return [
        (pk, sb, sigs[i].signature)
        for pk, sb, i in zip(pub_keys, sbs, idxs)
    ]


def _crypto_pass(
    chain_id: str, vals: ValidatorSet, commit: Commit,
    entries: list[_Entry], agg_pubs: list, groups: list[list[_Entry]],
    root,
) -> None:
    """Every signature of ``entries`` checked, or
    InvalidCommitSignatures; ``root`` is the ``verify_commit`` span."""
    has_agg = bool(commit.agg_signature)
    # crypto pass — one batch launch per key type in the commit; with
    # multiple key types the groups run CONCURRENTLY (the TPU kernel
    # waits on device compute and the native BLS library releases the
    # GIL, so a mixed mega-commit costs max(ed25519, bls) not the sum).
    # When the verify queue is live (crypto/verify_queue.py), each
    # signature consults the speculative-result cache first: votes the
    # queue already verified on receipt (VoteSet.add_vote) or via
    # blocksync prefetch skip the launch entirely — a fully speculated
    # commit performs ZERO new launches.  Fall-back is strict: cache
    # misses run the exact batch/serial verify below.
    spec_mtx = cmtsync.Mutex()
    spec = {"hits": 0, "misses": 0, "tier": None}
    # serving-plane lane (crypto/verify_queue.submission_lane):
    # captured ONCE here because groups may run on executor threads
    # where the caller's thread-local is invisible
    lane = _vq.active_submission_lane()

    def _verify_aggregate() -> None:
        """The commit-level BLS aggregate: one pairing-product over
        the covered signers' pubkey sum and the shared canonical
        message — verdicts land in the speculative cache under the
        same SHA-512 triple keying as per-signature facts (pubkeys ||
        aggregate signature || sign bytes), so a repeat verification
        of this commit (light-client re-sync, evidence re-check) is
        launch- and pairing-free."""
        msg = commit.aggregate_sign_bytes(chain_id)
        pk_bytes = b"".join(pk.bytes() for pk in agg_pubs)
        key: bytes | None = None
        if _vq.speculation_active():
            key = _vq.cache_key(pk_bytes, msg, commit.agg_signature)
            if _vq.cached_result(
                pk_bytes, msg, commit.agg_signature, key=key
            ) is True:
                with spec_mtx:
                    spec["hits"] += len(agg_pubs)
                return
            with spec_mtx:
                spec["misses"] += len(agg_pubs)
        from cometbft_tpu.crypto import bls_dispatch as _bls_dispatch

        verifier = _bls_dispatch.BlsLadderVerifier()
        try:
            verifier.set_aggregate(
                agg_pubs, msg, commit.agg_signature
            )
        except (TypeError, ValueError) as exc:
            # a non-BLS signer or malformed sizes: the commit is
            # malformed, not the tier — never a ladder fault
            raise InvalidCommitSignatures(
                f"malformed aggregate commit: {exc}"
            ) from exc
        ok, _results = verifier.verify()
        with spec_mtx:
            spec["tier"] = verifier._last_tier or spec["tier"] or "host"
        if key is not None:
            _vq.record_result(
                pk_bytes, msg, commit.agg_signature, ok, key=key
            )
        if not ok:
            raise InvalidCommitSignatures(
                "invalid BLS aggregate commit signature"
            )

    def _verify_group(group) -> None:
        pks = [vals.get_by_index(e.val_idx).pub_key for e in group]
        with _tracer.span(
            "verify_commit/sign_bytes", cat="crypto", sigs=len(group),
        ) as sb_span:
            # encoded: how many of the group's sign-bytes this span had
            # to encode (0 where a prefetch of this commit object did);
            # generic: how many of those the template did not cover
            sbs, encoded, generic = commit.vote_sign_bytes_many(
                chain_id, [e.idx for e in group]
            )
            sb_span.set(encoded=encoded, generic=generic)
        pending = list(range(len(group)))
        keys: list[bytes] | None = None
        if _vq.speculation_active():
            # only POSITIVE verdicts are ever cached (verify_queue
            # stores proofs of validity), so a hit is a signature that
            # skips its launch and anything else re-verifies below —
            # a transient mis-verify can never stick.  The SHA-512
            # prehash is computed ONCE per signature and reused by the
            # record_result below — on a cold 10k-sig commit the
            # consult-then-record shape would otherwise hash twice.
            with _tracer.span(
                "verify_commit/spec_lookup", cat="crypto", sigs=len(group),
            ) as lookup_span:
                keys = [
                    _vq.cache_key(
                        pks[i].bytes(), sbs[i],
                        commit.signatures[e.idx].signature,
                    )
                    for i, e in enumerate(group)
                ]
                pending = []
                hits = 0
                for i, e in enumerate(group):
                    if _vq.cached_result(
                        pks[i].bytes(), sbs[i],
                        commit.signatures[e.idx].signature,
                        key=keys[i],
                    ) is True:
                        hits += 1
                    else:
                        pending.append(i)
                # hits: how many of ``sigs`` the cache answered (all,
                # where a prefetch verified this commit)
                lookup_span.set(hits=hits)
            with spec_mtx:
                spec["hits"] += hits
                spec["misses"] += len(pending)
            if not pending:
                return
        if lane is not None and _vq.speculation_active():
            # serving-plane route: the pending signatures ride the
            # verify queue's lane (the light_client micro-batcher
            # coalesces CONCURRENT header syncs into single ladder
            # launches); verify_or_fallback keeps the strict sync
            # fallback and the launcher feeds the speculative cache,
            # so this branch never weakens the verdict
            items = [
                (
                    pks[i], sbs[i],
                    commit.signatures[group[i].idx].signature,
                )
                for i in pending
            ]
            results = _vq.verify_or_fallback(items, priority=lane)
            with spec_mtx:
                spec["tier"] = spec["tier"] or f"lane:{lane}"
            bad = next(
                (j for j, r in enumerate(results) if not r), None
            )
            if bad is not None:
                raise InvalidCommitSignatures(
                    f"wrong signature (#{group[pending[bad]].idx})"
                )
            return
        pk0 = pks[pending[0]]
        verifier = None
        if len(pending) >= 2 and crypto_batch.supports_batch_verifier(
            pk0
        ):
            verifier = crypto_batch.create_batch_verifier(pk0)
        if verifier is not None:
            for i in pending:
                verifier.add(
                    pks[i], sbs[i],
                    commit.signatures[group[i].idx].signature,
                )
            ok, results = verifier.verify()
            tier = getattr(verifier, "_last_tier", None)
            with spec_mtx:
                spec["tier"] = tier or spec["tier"] or "host"
            if _vq.speculation_active():
                # repeat verifications of this commit (evidence
                # re-checks, light-client retries) become cache hits
                with _tracer.span(
                    "verify_commit/record", cat="crypto",
                    sigs=len(pending),
                ):
                    for i, r in zip(pending, results):
                        _vq.record_result(
                            pks[i].bytes(), sbs[i],
                            commit.signatures[group[i].idx].signature,
                            bool(r),
                            key=keys[i] if keys is not None else None,
                        )
            if not ok:
                bad = next(j for j, r in enumerate(results) if not r)
                raise InvalidCommitSignatures(
                    f"wrong signature (#{group[pending[bad]].idx})"
                )
        else:
            # per-signature host fallback (secp256k1 and other key
            # types without a batch verifier, 1-sig groups): still ONE
            # ladder accounting sample at the decision point, so
            # crypto_dispatch_tier covers every verify in the process
            # — a raising (invalid) signature is a verdict the host
            # tier produced correctly, not a tier failure
            from cometbft_tpu.crypto.dispatch import LADDER as _ladder

            # deliberately NO batch/seconds here: this rung verifies
            # whatever key type fell through (secp256k1, 1-sig
            # groups) — timing it would pollute the host tier's
            # ed25519 cost estimates with unrelated crypto
            _ladder.note_batch("host")
            with spec_mtx:
                spec["tier"] = spec["tier"] or "host"
            for i in pending:
                sig = commit.signatures[group[i].idx].signature
                ok1 = pks[i].verify_signature(sbs[i], sig)
                if _vq.speculation_active():
                    _vq.record_result(
                        pks[i].bytes(), sbs[i], sig, ok1,
                        key=keys[i] if keys is not None else None,
                    )
                if not ok1:
                    raise InvalidCommitSignatures(
                        f"wrong signature (#{group[i].idx})"
                    )

    # one task per key-type group + (when the commit carries it) the
    # aggregate check — with several, they run CONCURRENTLY: the TPU
    # kernel waits on device compute and the native BLS library
    # releases the GIL, so a mixed aggregate+ed25519 commit costs
    # max(aggregate, ed25519), not the sum
    tasks = [lambda g=g: _verify_group(g) for g in groups]
    if agg_pubs:
        tasks.append(_verify_aggregate)
    elif has_agg:
        raise InvalidCommitSignatures(
            "aggregate signature with no aggregated signatures"
        )
    root.set(
        sigs=len(entries) + max(0, len(agg_pubs) - sum(
            1 for e in entries if e.aggregated
        )),
        groups=len(tasks),
    )
    speculating = _vq.speculation_active()
    try:
        if len(tasks) <= 1:
            for task in tasks:
                task()
        else:
            import concurrent.futures as _futures

            with _futures.ThreadPoolExecutor(len(tasks)) as pool:
                futs = [pool.submit(t) for t in tasks]
                for f in futs:
                    f.result()  # re-raises InvalidCommitSignatures
    finally:
        if speculating:
            # tier tells the flight tail whether a slow commit came
            # from a cold queue (misses ran on a real tier) or a
            # warm one (all hits -> "speculative", no launch)
            tier = (
                "speculative" if spec["misses"] == 0
                else (spec["tier"] or "host")
            )
            root.set(
                spec_hits=spec["hits"], spec_misses=spec["misses"],
                tier=tier,
            )
            FLIGHT.record(
                "consensus/speculative_verify",
                height=commit.height, sigs=len(entries),
                hits=spec["hits"], misses=spec["misses"], tier=tier,
            )


def verify_commit(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
) -> None:
    """Full verification: every signature (commit and nil votes) checked,
    +2/3 of total power must have signed the block (validation.go:28)."""
    _check_dims(vals, commit, height, block_id)
    needed = vals.total_voting_power() * 2 // 3
    _verify(
        chain_id,
        vals,
        commit,
        needed,
        count_sig=lambda cs: not cs.is_absent(),
        count_all=True,
        lookup_by_address=False,
        mode="full",
    )
    trustguard.note_validated("verify_commit")


def verify_commit_light(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    count_all: bool = False,
) -> None:
    """Verify only until +2/3 is reached; nil votes skipped
    (validation.go:63).  ``count_all=True`` checks every commit
    signature with no early break (VerifyCommitLightAllSignatures),
    required when the commit is used as evidence — nil votes are still
    skipped, so a garbage nil entry can't poison otherwise-valid
    evidence."""
    _check_dims(vals, commit, height, block_id)
    needed = vals.total_voting_power() * 2 // 3
    _verify(
        chain_id,
        vals,
        commit,
        needed,
        count_sig=lambda cs: cs.is_commit(),
        count_all=count_all,
        lookup_by_address=False,
        mode="light",
    )
    trustguard.note_validated("verify_commit_light")


def verify_commit_light_trusting(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    trust_level: Fraction = Fraction(1, 3),
    count_all: bool = False,
    signer_vals: ValidatorSet | None = None,
) -> None:
    """Light-client trusting verification: signatures matched by address
    against the *trusted* set; needs > trust_level of its power
    (validation.go:129).  ``count_all=True`` checks every signature with
    no early break (VerifyCommitLightTrustingAllSignatures), required
    when the commit is used as evidence.  ``signer_vals`` (the new
    block's own validator set) resolves aggregate signers outside the
    trusted set when the commit carries a BLS aggregate — see
    ``_verify``; without it an aggregate commit whose signer set has
    rotated past the trusted one fails loudly rather than verifying a
    truncated pairing equation."""
    if trust_level.denominator == 0:
        raise ValueError("trust level has zero denominator")
    if not (0 < trust_level <= 1):
        raise ValueError(f"trust level must be in (0, 1], got {trust_level}")
    needed = (
        vals.total_voting_power() * trust_level.numerator
    ) // trust_level.denominator
    _verify(
        chain_id,
        vals,
        commit,
        needed,
        count_sig=lambda cs: cs.is_commit(),
        count_all=count_all,
        lookup_by_address=True,
        mode="trusting",
        signer_vals=signer_vals,
    )
    trustguard.note_validated("verify_commit_light_trusting")
