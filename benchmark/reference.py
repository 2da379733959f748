"""The plain reference, the comparison that decides `correct`, and its control.

The reference is MQTT 5.0's topic-filter semantics (section 4.7),
written straight from the specification and importing nothing of the
broker: a filter matches a topic when it equals the topic with some of
its levels replaced by "+" and, optionally, its tail (possibly empty)
replaced by "#"; a topic starting with "$" is not matched by a filter
whose first level is a wildcard. So the filters that match a topic are
found by building, for each distinct filter shape ("mask") in the
table, the one filter string of that shape the topic could match, and
looking it up. A client receives a publish once if any of its filters
match (the broker delivers once per client, MQTT 5.0 section 4.7.2
note on overlapping subscriptions, without subscription identifiers).

The control is the same match at fingerprint precision: each literal
level is compared by a `bits`-wide hash of its text instead of the
text itself, which is the shortcut a hash-probe matcher tempts (trust
the fingerprint, skip the exact verify). It breaks the exact-match
guarantee the configurations state, and the comparison must catch it.
"""

from __future__ import annotations

import collections
import zlib
from typing import Dict, Iterable, List, Set, Tuple

import numpy as np


def candidate(mask: Tuple[str, ...], words: List[str]):
    """The one filter of shape `mask` that can match a topic of these
    words, or None when the shape cannot match."""
    n = len(words)
    if mask[-1] == "#":
        p = len(mask) - 1
        if n < p:
            return None
        return "/".join(
            [words[k] if mask[k] == "L" else "+" for k in range(p)] + ["#"]
        )
    if n != len(mask):
        return None
    return "/".join(words[k] if mask[k] == "L" else "+" for k in range(n))


class Reference:
    """Clients that must receive a publish, from (client, filter) pairs."""

    def __init__(self, subs: Iterable[Tuple[int, str]]):
        # filter -> its client, or the set of its clients when several
        self.dests: Dict[str, object] = {}
        dests = self.dests
        for client, flt in subs:
            cur = dests.get(flt)
            if cur is None:
                dests[flt] = client
            elif isinstance(cur, set):
                cur.add(client)
            elif cur != client:
                dests[flt] = {cur, client}
        self.masks = sorted({
            tuple(w if w in ("+", "#") else "L" for w in f.split("/"))
            for f in dests
        })

    def filters(self, topic: str) -> List[str]:
        words = topic.split("/")
        dollar = topic.startswith("$")
        out = []
        for m in self.masks:
            if dollar and m[0] != "L":
                continue
            c = candidate(m, words)
            if c is not None and c in self.dests:
                out.append(c)
        return out

    def receivers(self, topic: str) -> Set[int]:
        got: Set[int] = set()
        for f in self.filters(topic):
            d = self.dests[f]
            if isinstance(d, set):
                got |= d
            else:
                got.add(d)
        return got


class Control(Reference):
    """The reference with every literal level compared by a `bits`-wide
    hash (CRC-32 of its text, truncated): the fingerprint-precision
    match."""

    def __init__(self, subs: Iterable[Tuple[int, str]], bits: int):
        self.mask = (1 << bits) - 1
        super().__init__(
            (c, "/".join(self._h(w) for w in f.split("/"))) for c, f in subs
        )

    def _h(self, w: str) -> str:
        if w in ("+", "#"):
            return w
        return str(zlib.crc32(w.encode()) & self.mask)

    def receivers(self, topic: str) -> Set[int]:
        return super().receivers("/".join(self._h(w) for w in topic.split("/")))


# --- the comparison ---------------------------------------------------------


# Device-path counters of the program's telemetry that must stay 0 over a
# window: a batch the breaker re-served or sent to the host, a publish
# traced onto the host path, a compile while serving, a fanout resolved
# on the host instead of the device, an audit that disagreed.
DEVICE_PATH_ZERO = (
    "breaker_fallback_total",
    "breaker_degraded_batches_total",
    "traced_host_publish_total",
    "recompiles_at_serve_total",
    "fanout_host_fallback_total",
    "audit_divergence_total",
)
# The share (%) of the window's device match batches re-matched on the
# host trie. The kernel sends a batch there when a probe pair has more
# than two lanes whose fingerprint byte matches (its exactness rule), so
# sound runs read a little above 0; a path that answers from the host
# reads 100.
HOST_FALLBACK_PCT = 2.0


class Verdict:
    """The numbers compared, each with its limit, and the run's counts."""

    LIMITS = {
        # publishes whose receivers differ from the reference's: a
        # client missing, an extra client, or a client served twice
        "mismatched_publishes": 0,
        # publishes never answered: QoS 1 with no PUBACK, or accepted
        # and never delivered to anyone
        "unanswered_publishes": 0,
        # QoS 1 publishes PUBACKed before their in-process delivery
        "acked_before_delivery": 0,
        **{k: 0 for k in DEVICE_PATH_ZERO},
        "host_fallback_pct": HOST_FALLBACK_PCT,
    }

    def __init__(self):
        self.values = {k: 0 for k in self.LIMITS}
        self.attempted = 0
        self.refused = 0
        self.examples: List[str] = []

    @property
    def correct(self) -> bool:
        return all(self.values[k] <= lim for k, lim in self.LIMITS.items())

    @property
    def failed(self) -> int:
        return self.refused + self.values["unanswered_publishes"]

    def checks(self) -> Dict[str, Dict[str, float]]:
        return {
            k: {"value": self.values[k], "limit": lim}
            for k, lim in self.LIMITS.items()
        }

    def device_path(self, counters: Dict[str, int]) -> None:
        """Judges the window's device-path counters (deltas)."""
        for k in DEVICE_PATH_ZERO:
            self.values[k] = int(counters.get(k, 0))
        batches = counters.get("dispatch_batches_total", 0)
        host = counters.get("host_fallback_total", 0)
        self.values["host_fallback_pct"] = (
            100.0 * host / batches if batches else 100.0 * (host > 0)
        )
        for k in DEVICE_PATH_ZERO + ("host_fallback_pct",):
            if self.values[k] > self.LIMITS[k] and len(self.examples) < 5:
                self.examples.append(f"{k}: {self.values[k]}")

    def note(self, what: str, msg: str) -> None:
        self.values[what] += 1
        if len(self.examples) < 5:
            self.examples.append(f"{what}: {msg}")


def group_deliveries(msg: np.ndarray, client: np.ndarray) -> Dict[int, List[int]]:
    """msg id -> clients that received it (with repeats)."""
    out: Dict[int, List[int]] = collections.defaultdict(list)
    for m, c in zip(msg.tolist(), client.tolist()):
        out[m].append(c)
    return out


def compare(
    pubs: dict,
    topic_of,
    receivers_of,
    delivered: Dict[int, List[int]],
    local_done: Dict[int, int],
    shed: int,
) -> Verdict:
    """Judge every publish of the window.

    `pubs` holds the generator's records (msg, device, qos, ack, code);
    `receivers_of(topic)` is the reference (or, for the control, what is
    judged in the program's place); `delivered` maps msg id to receiving
    clients; `local_done` maps msg id to its last in-process delivery
    (monotonic ns); `shed` is how many publishes the broker counted as
    refused for overload (a QoS 0 publish has no answer to say so)."""
    v = Verdict()
    v.attempted = len(pubs["msg"])
    qos0_lost = 0
    quota_refused = 0
    for m, dev, q, ack, code in zip(
        pubs["msg"].tolist(), pubs["device"].tolist(), pubs["qos"].tolist(),
        pubs["ack"].tolist(), pubs["code"].tolist(),
    ):
        got = collections.Counter(delivered.get(m, ()))
        topic = topic_of(dev)
        if q and code >= 0x80:
            v.refused += 1
            quota_refused += code == 0x97
            if got:
                v.note("mismatched_publishes", f"{topic}: refused {code:#x} but delivered")
            continue
        if q and code < 0:
            v.note("unanswered_publishes", f"{topic}: no PUBACK")
            continue
        want = receivers_of(topic)
        if not got and want:
            if q:
                v.note("unanswered_publishes", f"{topic}: PUBACK {code:#x}, never delivered")
            else:
                qos0_lost += 1
            continue
        if len(got) != len(want) or any(
            n != 1 or c not in want for c, n in got.items()
        ):
            v.note(
                "mismatched_publishes",
                f"{topic}: want {sorted(want)[:8]} got {sorted(got.items())[:8]}",
            )
            continue
        if q and m in local_done and ack < local_done[m]:
            v.note("acked_before_delivery", f"{topic}: PUBACK before delivery")
    # QoS 0 publishes that reached no one: refused for overload as far
    # as the broker's shed counter covers them, never answered beyond it
    covered = min(qos0_lost, max(0, shed - quota_refused))
    v.refused += covered
    for _ in range(qos0_lost - covered):
        v.note("unanswered_publishes", "QoS 0 publish delivered to no one")
    return v
