"""The one traffic generator: reads a mix's parameters (a JSON file under
``bench/traffic/``) and the cell's fixed offered rate, and makes every
input of a run from ``--seed``.

A run serves ``n_slots`` chat sessions that are all resident on the
chip.  Before the window each session ingests its prior context chunks
(how many drawn from ``prior_turns``), so memories sit at every depth;
in the window, turns arrive on a Poisson schedule at the cell's rate
and each goes to one session.  A
turn is one ``ingest`` of a context chunk followed by one ``query`` of a
user message.  Every seed gets the same arrival times and the same
multiset of depths and lengths (drawn once from the mix's ``pool_seed``)
in its own order, with its own token ids and its own choice of
sessions, so two seeds do the same amount of work.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

Event = Tuple[str, np.ndarray]          # (op, tokens)


@dataclasses.dataclass
class Turn:
    index: int
    due: float                 # seconds after the window opens
    sid: str
    chunk: np.ndarray
    query: np.ndarray

    @property
    def tokens(self) -> int:
        return int(self.chunk.size + self.query.size)


@dataclasses.dataclass
class Plan:
    sessions: List[str]
    warmup: List[List[Tuple[str, str, np.ndarray]]]   # drains of submits
    history: Dict[str, List[Event]]    # every event before the window
    turns: List[Turn]                  # the window, in due order


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def bucket_of(n: int, ladder) -> int:
    for b in sorted(ladder):
        if n <= b:
            return b
    return n


def warmup_shapes(mix: dict, engine: dict) -> List[Tuple[str, int, int,
                                                          bool]]:
    """Every (op, batch bucket, token bucket, masked) fused-step shape the
    mix can make under the configuration's ladders."""
    shapes = []
    for op, key in (("ingest", "context_tokens"), ("query", "query_tokens")):
        lo, hi = mix[key]["min"], mix[key]["max"]
        by_bucket: Dict[int, List[int]] = {}
        for n in range(lo, hi + 1):
            by_bucket.setdefault(bucket_of(n, engine["token_buckets"]),
                                 []).append(n)
        for t, ns in sorted(by_bucket.items()):
            for masked in (False, True):
                if (t in ns) if not masked else any(n < t for n in ns):
                    shapes += [(op, b, t, masked)
                               for b in engine["batch_buckets"]]
    return shapes


def make_plan(mix: dict, engine: dict, rate: float, seconds: float,
              seed: int, vocab: int) -> Plan:
    """Everything a run submits, from the mix, the configuration's engine
    settings, the cell's rate and the run's seed."""
    n = engine["n_slots"]
    cache_len = engine["cache_len"]
    max_steps = engine["max_steps"]
    pool = np.random.default_rng(mix["pool_seed"])
    rng = np.random.default_rng(int(seed))

    def toks(k):
        return rng.integers(0, vocab, int(k), dtype=np.int32)

    sessions = [f"u{i:04d}" for i in range(n)]
    pt = mix["prior_turns"]
    depths = rng.permutation(pool.integers(pt["min"], pt["max"] + 1, n))
    p_chunk = list(rng.permutation(_lengths(mix["context_tokens"],
                                            int(depths.sum()), pool)))
    # prior turns are context chunks already ingested; the first prior
    # chunk of a session that has one may host a warm-up shape, and the
    # warm-up's queries follow their host's first chunk
    prior = {s: [int(p_chunk.pop()) for _ in range(d)]
             for s, d in zip(sessions, depths)}
    hosts = [s for s in sessions if prior[s]]
    n_real = {b: (1 if i == 0 else engine["batch_buckets"][i - 1] + 1)
              for i, b in enumerate(engine["batch_buckets"])}
    warm = []                           # drains of (sid, op, length)
    cursor = {"ingest": 0, "query": 0}
    for op, b, t, masked in warmup_shapes(mix, engine):
        k = n_real[b]
        if cursor[op] + k > len(hosts):
            raise ValueError("too few sessions with a history to host "
                             "the warm-up")
        group = hosts[cursor[op]:cursor[op] + k]
        cursor[op] += k
        warm.append([(s, op, t - 1 if masked else t) for s in group])
    if cursor["query"] > cursor["ingest"]:
        raise ValueError("warm-up queries need hosts that ingested first")
    first_query = {}
    for drain in warm:
        for s, op, length in drain:
            if op == "ingest":
                prior[s][0] = length
            else:
                first_query[s] = length
    history: Dict[str, List[Event]] = {s: [] for s in sessions}
    mem = {s: 0 for s in sessions}
    cached = {s: 0 for s in sessions}
    for s in sessions:
        for j, c in enumerate(prior[s]):
            history[s].append(("ingest", toks(c)))
            mem[s] += 1
            if j == 0 and s in first_query:
                history[s].append(("query", toks(first_query[s])))
                cached[s] += first_query[s]
    # the warm-up drains submit the hosts' first events
    warm_drains = [[(s, op, history[s][0 if op == "ingest" else 1][1])
                    for s, op, _ in drain] for drain in warm]

    # the window: Poisson arrivals, the same times for every seed
    times, t = [], 0.0
    while t < seconds:
        times.append(t)
        t += pool.exponential(1.0 / rate)
    w_chunk = rng.permutation(_lengths(mix["context_tokens"], len(times),
                                       pool))
    w_query = rng.permutation(_lengths(mix["query_tokens"], len(times),
                                       pool))
    think = float(mix["think_s"])
    last = {s: -np.inf for s in sessions}
    turns = []
    for i, (due, c, q) in enumerate(zip(times, w_chunk, w_query)):
        ok = [s for s in sessions
              if mem[s] < max_steps and cached[s] + q <= cache_len
              and due - last[s] >= think]
        if not ok:
            raise ValueError(f"no session can take turn {i} at {due:.3f} s:"
                             " the mix needs more sessions or room")
        s = ok[int(rng.integers(len(ok)))]
        mem[s] += 1
        cached[s] += int(q)
        last[s] = due
        turns.append(Turn(i, float(due), s, toks(c), toks(q)))
    return Plan(sessions, warm_drains, history, turns)
