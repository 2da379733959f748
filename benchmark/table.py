"""Deployment tables and traffic draws, from a configuration and a seed.

Pure Python and numpy: the load generator imports this module too, and
it must never touch JAX. Everything here is a function of the
configuration file (`benchmark/configs/<name>.json`), the traffic file
(`benchmark/traffic/<name>.json`) and `--seed`, so the harness, the
generator processes and the reference all rebuild the same rows without
passing them around.

A configuration describes its rows level by level. Row `i` has one word
per level:

  {"prefix": "t", "of": "mod", "card": 997}   -> "t" + str(i % 997)
  {"prefix": "n", "of": "div", "by": 1000}    -> "n" + str(i // 1000)
  {"prefix": "s", "of": "hash", "card": 100}  -> "s" + str(h(seed, k, i) % 100)
  {"prefix": "d", "of": "row"}                -> "d" + str(i)
  {"word": "m"}                               -> "m"

A prefix may be left out (no prefix).

Row `i`'s device topic is its words joined by "/". Its filter applies
skeleton `i % len(skeletons)`: a skeleton such as "L/L/L/+/L/#" keeps
the row's word where it says L, puts "+" where it says +, and ends with
"#" where it says #.

Socket subscribers' filters are templates over the rows of a seeded
"hot" permutation: "{L0@3}" is level 0's word of hot row 3, and
"{F@2}" is the whole filter of hot row 2.

A traffic mix names its topic draw as data: "uniform" over the rows, or
{"zipf": s}, rows ranked by a seeded permutation and drawn with weight
rank^-s.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, List

import numpy as np

WARM_BIT = 1 << 62  # message ids of warm-up publishes carry this bit
_TEMPLATE = re.compile(r"\{(L(\d+)|F)@(\d+)\}")
_M64 = (1 << 64) - 1


def _mix(seed: int, level: int, rows: np.ndarray) -> np.ndarray:
    """A seeded 64-bit mix of row ids (splitmix64 finaliser)."""
    with np.errstate(over="ignore"):
        z = rows.astype(np.uint64) + np.uint64(
            (seed * 0x9E3779B97F4A7C15 + level * 0xBF58476D1CE4E5B9) & _M64
        )
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


class Table:
    """The rows of one configuration under one seed."""

    def __init__(self, conf: dict, seed: int):
        self.conf = conf
        self.seed = int(seed)
        self.n = int(conf["filters"])
        self.levels: List[dict] = conf["levels"]
        self.skeletons: List[List[str]] = [
            s.split("/") for s in conf["skeletons"]
        ]
        for sk in self.skeletons:
            if len(sk) > len(self.levels) + 1 or "#" in sk[:-1]:
                raise ValueError(f"bad skeleton {'/'.join(sk)}")
        # the per-level word ids of the hashed levels, computed once
        self._hashed: Dict[int, np.ndarray] = {}
        rows = np.arange(self.n, dtype=np.int64)
        for k, lv in enumerate(self.levels):
            if lv.get("of") == "hash":
                self._hashed[k] = (
                    _mix(self.seed, k, rows) % np.uint64(lv["card"])
                ).astype(np.int64)

    def word(self, i: int, k: int) -> str:
        lv = self.levels[k]
        of = lv.get("of")
        if of is None:
            return lv["word"]
        p = lv.get("prefix", "")
        if of == "row":
            return f"{p}{i}"
        if of == "mod":
            return f"{p}{i % lv['card']}"
        if of == "div":
            return f"{p}{i // lv['by']}"
        return f"{p}{self._hashed[k][i]}"

    def words(self, i: int) -> List[str]:
        return [self.word(i, k) for k in range(len(self.levels))]

    def topic(self, i: int) -> str:
        """The device topic of row i (no wildcards)."""
        return "/".join(self.words(i))

    def filter(self, i: int) -> str:
        ws = self.words(i)
        out = []
        for k, s in enumerate(self.skeletons[i % len(self.skeletons)]):
            if s == "#":
                out.append("#")
                break
            out.append(ws[k] if s == "L" else "+")
        return "/".join(out)

    def _column(self, k: int) -> List[str]:
        """Level k's word for every row, as one list."""
        lv = self.levels[k]
        of = lv.get("of")
        if of is None:
            return [lv["word"]] * self.n
        p = lv.get("prefix", "")
        if of == "row":
            return [f"{p}{i}" for i in range(self.n)]
        if of == "div":
            return [f"{p}{i // lv['by']}" for i in range(self.n)]
        vocab = [f"{p}{j}" for j in range(lv["card"])]
        if of == "mod":
            return [vocab[i % lv["card"]] for i in range(self.n)]
        return [vocab[j] for j in self._hashed[k].tolist()]

    def filters(self) -> List[str]:
        """Every row's filter, built level by level (row order)."""
        cols = [self._column(k) for k in range(len(self.levels))]
        n_sk = len(self.skeletons)
        out: List[str] = [""] * self.n
        for s, sk in enumerate(self.skeletons):
            rows = range(s, self.n, n_sk)
            parts = []
            for k, tok in enumerate(sk):
                if tok == "#":
                    parts.append(["#"] * len(rows))
                    break
                parts.append(
                    [cols[k][i] for i in rows] if tok == "L" else ["+"] * len(rows)
                )
            out[s::n_sk] = ["/".join(p) for p in zip(*parts)]
        return out

    def holder(self, i: int) -> int:
        """The in-process session that holds filter i."""
        return i % int(self.conf["sessions"])

    def hot_rows(self) -> np.ndarray:
        """A seeded permutation of the rows; socket filters name its head."""
        return np.random.default_rng([self.seed, 7]).permutation(self.n)

    def socket_filters(self) -> List[str]:
        hot = self.hot_rows()

        def fill(m: re.Match) -> str:
            row = int(hot[int(m.group(3))])
            if m.group(1) == "F":
                return self.filter(row)
            return self.word(row, int(m.group(2)))

        return [_TEMPLATE.sub(fill, t) for t in self.conf["socket_subscribers"]]


# --- traffic draws ---------------------------------------------------------


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def poisson_offsets(n: int, seconds: float, seed: int, stream: int) -> np.ndarray:
    """Arrival offsets (seconds) of n publishes over `seconds`: the gaps
    are the n quantiles of an exponential distribution in a seeded
    order, scaled to fill the span exactly. Every seed offers the same
    gaps, so seeds differ in order only, not in load."""
    q = (np.arange(n, dtype=np.float64) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps = _rng(seed, stream).permutation(gaps)
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps) - gaps  # the first publish is due at 0


def qos_draw(n: int, qos1_share: float, seed: int, stream: int) -> np.ndarray:
    """Exactly round(n * share) QoS 1 publishes, in a seeded order."""
    q = np.zeros(n, np.int8)
    q[: int(round(n * qos1_share))] = 1
    return _rng(seed, stream).permutation(q)


def check_draw(draw) -> None:
    """Refuses a topic draw the generator cannot make."""
    if draw == "uniform":
        return
    if (
        isinstance(draw, dict) and list(draw) == ["zipf"]
        and isinstance(draw["zipf"], (int, float)) and draw["zipf"] > 0
    ):
        return
    raise ValueError(f"topic_draw {draw!r}: 'uniform' or {{\"zipf\": s}} with s > 0")


@functools.lru_cache(maxsize=4)
def _zipf_cdf(n_rows: int, s: float) -> np.ndarray:
    w = np.arange(1, n_rows + 1, dtype=np.float64) ** -s
    c = np.cumsum(w)
    return c / c[-1]


@functools.lru_cache(maxsize=4)
def _ranked_rows(n_rows: int, seed: int) -> np.ndarray:
    """Rows by popularity rank: the same in every stream of one seed."""
    return _rng(seed, 8).permutation(n_rows)


def device_draw(n: int, n_rows: int, seed: int, stream: int,
                draw="uniform") -> np.ndarray:
    """Device (row) ids of n publishes, by the mix's `topic_draw`."""
    check_draw(draw)
    rng = _rng(seed, stream)
    if draw == "uniform":
        return rng.integers(0, n_rows, size=n, dtype=np.int64)
    cdf = _zipf_cdf(n_rows, float(draw["zipf"]))
    rank = np.minimum(np.searchsorted(cdf, rng.random(n), side="right"), n_rows - 1)
    return _ranked_rows(n_rows, int(seed))[rank].astype(np.int64)


def payload_size(traffic: dict) -> int:
    return max(16, int(traffic["payload_bytes"]))
