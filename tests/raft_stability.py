"""Shared election-stability helper for the raft/cluster test fixtures.

Deflake contract (ISSUE 2 satellite): at startup every node races its first
election, and a second candidate can depose the first winner moments after
a test grabbed it (~10-30% of runs under load). A leader only counts once
it has SURVIVED one full election timeout in the same term — by then every
peer has seen its heartbeats and won't start a rival election — and has
committed an entry of its own term (raft §8 ``leadership_settled``), so
replicate/read assertions built on it hold.

Not collected by pytest (no ``test_`` prefix); imported by test_raft.py and
test_cluster.py, which differ only in how a node's consensus is reached.
"""

from __future__ import annotations

import asyncio
import functools
import re
from typing import Callable


async def wait_for_stable_leader(
    find_leader: Callable,
    get_consensus: Callable,
    election_timeout_s: float,
    timeout: float = 16.0,
    what: str = "leader",
    margin: float = 1.0,
):
    """Return the first node whose leadership survives ``margin`` election
    timeouts in-term with §8 settled; AssertionError after ``timeout``.

    ``margin`` is the per-test knob: 1.0 (one full election timeout) is
    enough for most fixtures; tests that immediately pile replication load
    or membership churn onto the fresh leader pass 1.5-2.0 so a SECOND
    startup-election wave (a slow node whose first timeout fires late) has
    provably come and gone before the test builds on the leader."""
    deadline = asyncio.get_event_loop().time() + timeout
    while asyncio.get_event_loop().time() < deadline:
        node = find_leader()
        if node is None:
            await asyncio.sleep(0.02)
            continue
        c = get_consensus(node)
        term = c.term
        await asyncio.sleep(election_timeout_s * margin)
        c = get_consensus(node)
        if (
            c is not None
            and c.is_leader()
            and c.term == term
            and c.leadership_settled()
        ):
            return node
    raise AssertionError(f"no stable {what} within timeout")


# Failure signatures of mid-test re-election thrash — the residual flake
# class the stable-leader wait cannot remove (a leader that settled can
# still be deposed SECONDS later when heavy load delays its heartbeats).
# "timeout: <msg>" is test_cluster.wait_until's liveness-wait signature:
# every wait_until/wait_converged in the decorated tests waits on leader
# presence or leader-driven convergence, so its timeout under load IS the
# thrash symptom; data-correctness asserts there are plain asserts with
# other messages and still fail attempt 1.
_ELECTION_THRASH_RE = re.compile(
    r"no (stable|controller) .*leader|leader.*(deposed|changed|lost)"
    r"|not_leader|no live leader|election|timeout: ",
    re.IGNORECASE,
)


def flaky_election_retry(reason: str, times: int = 2):
    """Reasoned retry wrapper for the documented load-sensitive tests.

    Retries ONLY failures matching the election-thrash signatures above —
    a data-loss or protocol assertion still fails on the first attempt.
    Each retry runs under a FRESH tmp_path subdirectory: the fixtures
    persist raft logs under tmp_path/n{i}, so a rebuilt cluster over the
    same dirs would replay attempt 1's controller commands (create_topic
    -> TopicExistsError) and the retry could never pass.
    ``reason`` is mandatory, suppression-pragma style: the decoration
    documents WHY this test is allowed to retry (keep it to mid-test
    re-election under CI load, nothing else)."""
    assert reason, "flaky_election_retry requires a reason"

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            base = kwargs.get("tmp_path")  # pytest passes fixtures by name
            for attempt in range(times):
                if base is not None and attempt:
                    retry_dir = base / f"retry{attempt}"
                    retry_dir.mkdir(exist_ok=True)
                    kwargs["tmp_path"] = retry_dir
                try:
                    return fn(*args, **kwargs)
                except (AssertionError, TimeoutError) as e:
                    last = e
                    # a bare TimeoutError (asyncio.wait_for; often empty
                    # str) is a liveness failure by definition — retryable
                    thrash = isinstance(e, TimeoutError) or bool(
                        _ELECTION_THRASH_RE.search(str(e))
                    )
                    if attempt + 1 >= times or not thrash:
                        raise
            raise last  # pragma: no cover — loop always returns or raises

        return wrapper

    return deco
